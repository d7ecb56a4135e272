"""Static analysis + runtime guards for the repo's TPU invariants.

``jaxlint`` is the AST pass (``python -m
dalle_pytorch_tpu.analysis.jaxlint`` or the ``jaxlint`` console script);
``guards`` is its runtime twin (``no_transfers``, ``compile_count``).
Rule catalog and rationale: docs/STATIC_ANALYSIS.md.
"""

from dalle_pytorch_tpu.analysis.guards import (CompileCountError,  # noqa: F401
                                               compile_count, counting,
                                               no_transfers)

_JAXLINT_NAMES = ("RULES", "Finding", "lint_file", "lint_source")


def __getattr__(name):
    # lazy: `python -m ...analysis.jaxlint` warns if the package
    # __init__ already imported the submodule before runpy runs it
    if name in _JAXLINT_NAMES:
        from dalle_pytorch_tpu.analysis import jaxlint
        return getattr(jaxlint, name)
    raise AttributeError(name)

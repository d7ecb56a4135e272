"""The one jit-donation gate the parallel and serve packages share."""

from __future__ import annotations


def donate_if_accelerator(*argnums: int) -> tuple:
    """``donate_argnums`` for jit, gated to real accelerators: ``()`` on
    the CPU backend. CPU "donation" is a warning at best, and under the
    persistent compilation cache it can MIS-ALIAS sharded buffers —
    donated params came back as garbage in a resumed-run checkpoint
    before every donation site adopted this gate. One definition keeps
    the hazard and its fix in one place; the next donation site should
    call this, not hand-roll the backend check."""
    import jax
    return tuple(argnums) if jax.default_backend() != "cpu" else ()

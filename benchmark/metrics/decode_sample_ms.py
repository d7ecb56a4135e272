"""Device milliseconds of one fused decode step under the scope ``sample``:
the forbidden-position mask, the temperature, the per-slot top-k threshold,
the nucleus threshold where a live slot asks for it, and the categorical
draw."""

from benchmark import scopes


def read(ctx):
    if ctx["kind"] != "serve":
        return None
    steps = int(ctx["cell"].spec["engine"]["chunk_steps"])
    return scopes.scope_ms(ctx, r"decode_impl", ("sample",), per=steps)

"""Device milliseconds of one fused decode step under the scope ``kv.view``:
the page pool (or the cache) turned into per-slot rows, with the pool's
relayout copies that reach it."""

from benchmark import scopes


def read(ctx):
    if ctx["kind"] != "serve":
        return None
    steps = int(ctx["cell"].spec["engine"]["chunk_steps"])
    return scopes.scope_ms(ctx, r"decode_impl", ("kv.view",), per=steps)

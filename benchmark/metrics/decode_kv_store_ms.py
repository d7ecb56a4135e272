"""Device milliseconds of one fused decode step under the scope ``kv.store``:
the new rows' scatter into the cache or the pool, with the pool copies the
compiler makes for it."""

from benchmark import scopes


def read(ctx):
    if ctx["kind"] != "serve":
        return None
    steps = int(ctx["cell"].spec["engine"]["chunk_steps"])
    return scopes.scope_ms(ctx, r"decode_impl", ("kv.store",), per=steps)

"""Device milliseconds of one fused decode step under the scope
``kv.window``: a window layer's pool turned into per-slot rows (the gather
of its ring's pages through the ring tables) and the new rows' store into
it. None where the program has no such scope."""

from benchmark import scopes


def read(ctx):
    if ctx["kind"] != "serve":
        return None
    got = scopes.program_seconds(ctx, r"decode_impl")
    if got is None or "kv.window" not in got["seconds"]:
        return None
    steps = got["runs"] * int(ctx["cell"].spec["engine"]["chunk_steps"])
    return 1e3 * got["seconds"]["kv.window"] / steps

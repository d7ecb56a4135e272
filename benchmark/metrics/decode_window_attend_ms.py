"""Device milliseconds of one fused decode step under the scope
``attn.window``: a window layer's read of its gathered ring (the ring's
mask, q.k a key/value head's group at once, softmax, .v). None where the
program has no such scope."""

from benchmark import scopes


def read(ctx):
    if ctx["kind"] != "serve":
        return None
    got = scopes.program_seconds(ctx, r"decode_impl")
    if got is None or "attn.window" not in got["seconds"]:
        return None
    steps = got["runs"] * int(ctx["cell"].spec["engine"]["chunk_steps"])
    return 1e3 * got["seconds"]["attn.window"] / steps

"""Device milliseconds of one fused decode step under the scope
``moe.route``: the router, the top-k, the sort of the (token, pick) pairs by expert, their gather and the weighted sum back in token order. None where the program has no such scope."""

from benchmark import scopes


def read(ctx):
    if ctx["kind"] != "serve":
        return None
    got = scopes.program_seconds(ctx, r"decode_impl")
    if got is None or "moe.route" not in got["seconds"]:
        return None
    steps = got["runs"] * int(ctx["cell"].spec["engine"]["chunk_steps"])
    return 1e3 * got["seconds"]["moe.route"] / steps

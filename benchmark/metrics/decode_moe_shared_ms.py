"""Device milliseconds of one fused decode step under the scope
``moe.shared``: the shared experts' feed-forward. None where the program has no such scope."""

from benchmark import scopes


def read(ctx):
    if ctx["kind"] != "serve":
        return None
    got = scopes.program_seconds(ctx, r"decode_impl")
    if got is None or "moe.shared" not in got["seconds"]:
        return None
    steps = got["runs"] * int(ctx["cell"].spec["engine"]["chunk_steps"])
    return 1e3 * got["seconds"]["moe.shared"] / steps

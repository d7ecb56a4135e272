"""Device milliseconds of one fused decode step under the scope ``gmu``:
the gated memory units' two products and the gate. None where the program
has no such scope."""

from benchmark import scopes


def read(ctx):
    if ctx["kind"] != "serve":
        return None
    got = scopes.program_seconds(ctx, r"decode_impl")
    if got is None or "gmu" not in got["seconds"]:
        return None
    steps = got["runs"] * int(ctx["cell"].spec["engine"]["chunk_steps"])
    return 1e3 * got["seconds"]["gmu"] / steps

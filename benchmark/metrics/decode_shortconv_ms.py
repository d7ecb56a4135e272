"""Device milliseconds of one fused decode step under the scopes
``conv.proj`` and ``conv.mix``: the gated short convolutions' input and
output projections, and their two gates, the taps, and the tail's read,
roll and store. None where the program has no such scope."""

from benchmark import scopes


def read(ctx):
    if ctx["kind"] != "serve":
        return None
    got = scopes.program_seconds(ctx, r"decode_impl")
    if got is None or "conv.mix" not in got["seconds"]:
        return None
    steps = got["runs"] * int(ctx["cell"].spec["engine"]["chunk_steps"])
    return 1e3 * (got["seconds"]["conv.mix"]
                  + got["seconds"].get("conv.proj", 0.0)) / steps

"""Device milliseconds of one fused decode step under the scopes
``delta.proj`` and ``delta.rule``: the gated delta-rule layers' two input
projections and their output projection, and the convolution and its tail,
the norms and gates, the state's read, decay, update, readout and write
and the gated norm. None where the program has no such scope."""

from benchmark import scopes


def read(ctx):
    if ctx["kind"] != "serve":
        return None
    got = scopes.program_seconds(ctx, r"decode_impl")
    if got is None or "delta.rule" not in got["seconds"]:
        return None
    steps = got["runs"] * int(ctx["cell"].spec["engine"]["chunk_steps"])
    return 1e3 * (got["seconds"]["delta.rule"]
                  + got["seconds"].get("delta.proj", 0.0)) / steps

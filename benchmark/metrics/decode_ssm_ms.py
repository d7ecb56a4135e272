"""Device milliseconds of one fused decode step under the scopes
``ssm.proj`` and ``ssm.scan``: the state-space layers' four products, and
their convolution and the state's read, update, readout and write. None
where the program has no such scope."""

from benchmark import scopes


def read(ctx):
    if ctx["kind"] != "serve":
        return None
    got = scopes.program_seconds(ctx, r"decode_impl")
    if got is None or "ssm.scan" not in got["seconds"]:
        return None
    steps = got["runs"] * int(ctx["cell"].spec["engine"]["chunk_steps"])
    return 1e3 * (got["seconds"]["ssm.scan"]
                  + got["seconds"].get("ssm.proj", 0.0)) / steps

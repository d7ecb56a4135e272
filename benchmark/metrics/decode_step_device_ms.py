"""Device milliseconds of one fused decode step: the time of the decode
program's runs in the trace over the steps they held (``chunk_steps``
each)."""


def read(ctx):
    red = ctx["trace"]
    if red is None or ctx["kind"] != "serve":
        return None
    runs = red.module_runs(r"decode_impl")
    if not runs:
        return None
    steps = len(runs) * int(ctx["cell"].spec["engine"]["chunk_steps"])
    return 1e3 * sum(s for _, s in runs) / steps

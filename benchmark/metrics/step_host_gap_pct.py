"""Share of the traced window in which no train-step program was running
on the first chip: the host's part of a step (batch, placement,
dispatch) that the device waited for."""


def read(ctx):
    red = ctx["trace"]
    if red is None or ctx["kind"] != "train" or not red.window_s:
        return None
    runs = red.module_runs(r"jit_step")
    if not runs:
        return None
    return 100.0 * max(1.0 - sum(s for _, s in runs) / red.window_s, 0.0)

"""Share of the traced window in which no operation ran on the device
(mean over chips), training cells."""


def read(ctx):
    red = ctx["trace"]
    if red is None or ctx["kind"] != "train" or not red.window_s:
        return None
    return 100.0 * red.idle_share

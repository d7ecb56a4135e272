"""Share of the traced window in which no operation ran on the device,
serving cells."""


def read(ctx):
    red = ctx["trace"]
    if red is None or ctx["kind"] != "serve" or not red.window_s:
        return None
    return 100.0 * red.idle_share

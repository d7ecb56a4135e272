"""Share of the window the engine thread spent working on the host and not
waiting for the device: the ``engine_loop_s`` counter less
``harvest_wait_s``, differenced over the window, over its seconds."""

from benchmark import scopes


def read(ctx):
    if ctx["kind"] != "serve":
        return None
    loop = scopes.counter_delta(ctx, "engine_loop_s")
    wait = scopes.counter_delta(ctx, "harvest_wait_s")
    if loop is None or wait is None:
        return None
    return 100.0 * (loop - wait) / (ctx["t_close"] - ctx["t_open"])

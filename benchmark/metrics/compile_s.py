"""Seconds jax spent in backend compiles during set-up (the benchmark's
own ``jax.monitoring`` listener)."""


def read(ctx):
    return ctx["setup_compile"]["compile_s"]

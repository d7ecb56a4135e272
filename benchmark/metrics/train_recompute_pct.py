"""Share of the train step's device time whose path runs through
``jax.checkpoint``'s ``rematted_computation``: the forward computed again
in the backward."""

from benchmark import scopes


def read(ctx):
    if ctx["kind"] != "train":
        return None
    got = scopes.program_seconds(ctx, r"jit_step")
    if got is None or not got["total_s"]:
        return None
    return 100.0 * got["recompute_s"] / got["total_s"]

"""The routed experts' products' share of their roofline: the least time
the chip could take to read, once, the weights of the DISTINCT experts
that received a pick in a decode step (the engine's ``moe_experts_touched``
counter over ``decode_steps``, differenced over the window, x the family's
``flops.expert_bytes``, over the published bandwidth), over the measured
device time of the scope ``moe.experts`` a step. Memory-bound: a step's few
dozen rows are 0.3% of the operations that would bind. Under 100% by
construction: an expert that holds a row has to be read whole at least
once, and nothing but the touched experts is counted."""

import jax.numpy as jnp

from benchmark import scopes


def read(ctx):
    if ctx["kind"] != "serve":
        return None
    touched = scopes.counter_delta(ctx, "moe_experts_touched")
    steps = scopes.counter_delta(ctx, "decode_steps")
    flops = ctx["cell"].family.flops
    if touched is None or not steps or not hasattr(flops, "expert_bytes"):
        return None
    per = int(ctx["cell"].spec["engine"]["chunk_steps"])
    ms = scopes.scope_ms(ctx, r"decode_impl", ("moe.experts",), per=per)
    if not ms:
        return None
    nbytes = touched / steps * flops.expert_bytes(
        ctx["dims"], jnp.dtype(
            ctx["cell"].config["param_dtype"]).itemsize)
    least = nbytes / ctx["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least / (ms / 1e3)

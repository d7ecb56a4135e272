"""The recurrent mixers' share of their roofline: the least time the chip
could take to read, once, the weights of the state-space layers and of the
gated memory units and to read and write every slot's recurrent state in
a decode step (the family's ``flops.ssm_step_bytes``, over the published
bandwidth), over the measured device time of the scopes ``ssm.proj`` +
``ssm.scan`` + ``gmu`` a step. Memory-bound: a step's few dozen rows are
a fraction of a percent of the operations that would bind. Under 100% by
construction: every weight and every slot's state has to cross once.
None where the program has no such scopes or the family no such count."""

import jax.numpy as jnp

from benchmark import scopes


def read(ctx):
    if ctx["kind"] != "serve":
        return None
    flops = ctx["cell"].family.flops
    got = scopes.program_seconds(ctx, r"decode_impl")
    if not hasattr(flops, "ssm_step_bytes") or got is None \
            or "ssm.scan" not in got["seconds"]:
        return None
    per = int(ctx["cell"].spec["engine"]["chunk_steps"])
    ms = scopes.scope_ms(ctx, r"decode_impl", ("ssm.proj", "ssm.scan",
                                               "gmu"), per=per)
    if not ms:
        return None
    nbytes = flops.ssm_step_bytes(
        ctx["dims"], int(ctx["cell"].spec["num_slots"]),
        jnp.dtype(ctx["cell"].config["param_dtype"]).itemsize)
    least = nbytes / ctx["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least / (ms / 1e3)

"""The gated delta-rule layers' share of their roofline: the least time
the chip could take to read, once, the delta-rule mixers' projections and
taps and to read and write every slot's matrix state and convolution tail
in a decode step (the family's ``flops.delta_step_bytes``, over the
published bandwidth), over the measured device time of the scopes
``delta.proj`` + ``delta.rule`` a step. Memory-bound: the rule makes two
operations a number of the state it reads and writes. Under 100% by
construction: every weight and every slot's state has to cross once, and
the state once more on its way back. None where the program has no such
scopes or the family no such count."""

import jax.numpy as jnp

from benchmark import scopes


def read(ctx):
    if ctx["kind"] != "serve":
        return None
    flops = ctx["cell"].family.flops
    got = scopes.program_seconds(ctx, r"decode_impl")
    if not hasattr(flops, "delta_step_bytes") or got is None \
            or "delta.rule" not in got["seconds"]:
        return None
    per = int(ctx["cell"].spec["engine"]["chunk_steps"])
    ms = scopes.scope_ms(ctx, r"decode_impl", ("delta.proj", "delta.rule"),
                         per=per)
    if not ms:
        return None
    nbytes = flops.delta_step_bytes(
        ctx["dims"], int(ctx["cell"].spec["num_slots"]),
        jnp.dtype(ctx["cell"].config["param_dtype"]).itemsize)
    least = nbytes / ctx["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least / (ms / 1e3)

"""The gated short convolutions' share of their roofline: the least time
the chip could take to read, once, the short-convolution layers' input and
output projections and taps and to read and write every slot's tail in a
decode step (the family's ``flops.shortconv_step_bytes``, over the
published bandwidth), over the measured device time of the scopes
``conv.proj`` + ``conv.mix`` a step. Memory-bound: a step's few dozen rows
are a fraction of a percent of the operations that would bind. Under 100%
by construction: every weight and every slot's tail has to cross once.
None where the program has no such scopes or the family no such count."""

import jax.numpy as jnp

from benchmark import scopes


def read(ctx):
    if ctx["kind"] != "serve":
        return None
    flops = ctx["cell"].family.flops
    got = scopes.program_seconds(ctx, r"decode_impl")
    if not hasattr(flops, "shortconv_step_bytes") or got is None \
            or "conv.mix" not in got["seconds"]:
        return None
    per = int(ctx["cell"].spec["engine"]["chunk_steps"])
    ms = scopes.scope_ms(ctx, r"decode_impl", ("conv.proj", "conv.mix"),
                         per=per)
    if not ms:
        return None
    nbytes = flops.shortconv_step_bytes(
        ctx["dims"], int(ctx["cell"].spec["num_slots"]),
        jnp.dtype(ctx["cell"].config["param_dtype"]).itemsize)
    least = nbytes / ctx["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least / (ms / 1e3)

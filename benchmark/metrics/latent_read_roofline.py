"""The latent cache read's share of its roofline: the least time the chip
could take to read, once, the LIVE rows' distinct pages of every layer in
a decode step, over the measured device time of the scopes ``kv.view`` +
``attn.read`` a step. Live pages: the allocator's ``pages_in_use`` (the
mean of the window's two ends; a physical page is counted once however
many tables map it, and the trash page never) less one page a slot, the
most that is mapped ahead of the rows written. A page is ``page_size``
rows of the family's ``flops.latent_row_bytes`` in each layer. Under 100%
by construction: the gather reads every page of every slot's table, live
or not, so it moves at least these bytes. None where the engine has no
latent pool."""

import jax.numpy as jnp

from benchmark import scopes


def read(ctx):
    if ctx["kind"] != "serve":
        return None
    flops = ctx["cell"].family.flops
    s0, s1 = ctx.get("stats0") or {}, ctx.get("stats1") or {}
    if not hasattr(flops, "latent_row_bytes") or "pages_in_use" not in s1 \
            or "pages_in_use" not in s0:
        return None
    per = int(ctx["cell"].spec["engine"]["chunk_steps"])
    ms = scopes.scope_ms(ctx, r"decode_impl", ("kv.view", "attn.read"),
                         per=per)
    if not ms:
        return None
    d = ctx["dims"]
    live = max((s0["pages_in_use"] + s1["pages_in_use"]) / 2.0
               - int(ctx["cell"].spec["num_slots"]), 0.0)
    itemsize = jnp.dtype(
        ctx["cell"].config["param_dtype"]).itemsize
    nbytes = live * d.depth * int(s1["page_size"]) \
        * flops.latent_row_bytes(d, itemsize)
    least = nbytes / ctx["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least / (ms / 1e3)

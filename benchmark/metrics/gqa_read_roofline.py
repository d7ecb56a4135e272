"""The grouped-query cache reads' share of their roofline: the least time
the chip could take to read, once, the LIVE rows' distinct pages of both
pools in every layer of their type in a decode step, over the measured
device time of the scopes ``kv.view`` + ``kv.window`` + ``attn.read`` +
``attn.window`` a step. Live pages: each allocator's pages in use
(``full_pages_in_use``, ``window_pages_in_use``: the mean of the window's
two ends; the trash page never) less one page a slot, the most that is
mapped ahead of the rows written. The bytes are the family's
``flops.gqa_read_bytes``. Under 100% by construction: the gathers read
every page of every slot's table, live or not, and ``kv.window`` holds
the new rows' store besides. None where the engine has no window pool."""

import jax.numpy as jnp

from benchmark import scopes


def read(ctx):
    if ctx["kind"] != "serve":
        return None
    flops = ctx["cell"].family.flops
    s0, s1 = ctx.get("stats0") or {}, ctx.get("stats1") or {}
    if not hasattr(flops, "gqa_read_bytes") \
            or "window_pages_in_use" not in s1 \
            or "window_pages_in_use" not in s0:
        return None
    per = int(ctx["cell"].spec["engine"]["chunk_steps"])
    ms = scopes.scope_ms(ctx, r"decode_impl", (
        "kv.view", "kv.window", "attn.read", "attn.window"), per=per)
    if not ms:
        return None
    slots = int(ctx["cell"].spec["num_slots"])

    def live(key):
        return max((s0[key] + s1[key]) / 2.0 - slots, 0.0)

    nbytes = flops.gqa_read_bytes(
        ctx["dims"], live("full_pages_in_use"), live("window_pages_in_use"),
        int(s1["page_size"]),
        jnp.dtype(ctx["cell"].config["param_dtype"]).itemsize)
    least = nbytes / ctx["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least / (ms / 1e3)

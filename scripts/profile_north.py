"""Where does the north-config step's time go?

Poor-man's profiler: times each piece of the depth-12 train step IN
ISOLATION with host-fetch-synced chained executions — attention fwd+bwd (flash vs xla), the GEGLU/projection
matmuls, the 12k-vocab CE head (dense vs chunked), the embedding +
position lookups, and the adam update — then compares the sum against the
measured full step so the residual (XLA fusion wins, dispatch, data
movement) is visible.

Run on the chip: python scripts/profile_north.py [--batch 8] [--steps 10]
Prints one JSON line per piece plus a summary; all times are per-step ms.
"""

import argparse
import functools
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _time(fn, args, steps, fetch):
    """Wall ms/step for ``steps`` CHAINED fn calls, host-fetch synced.

    Chaining is real, not nominal: each iteration's first argument carries a
    zero-valued term data-dependent on the previous output (one fused
    elementwise add on one leaf), so the final ``fetch`` — a host round-trip
    on the last output — cannot complete until every iteration has executed.
    Same discipline as bench.time_steps."""
    import jax

    a0, rest = args[0], args[1:]

    @jax.jit
    def chained(a0, *rest):
        out = fn(a0, *rest)
        dep = jax.tree.leaves(out)[0].ravel()[0] * 0
        leaves, treedef = jax.tree.flatten(a0)
        leaves[0] = leaves[0] + dep.astype(leaves[0].dtype)
        return out, jax.tree.unflatten(treedef, leaves)

    out, a = chained(a0, *rest)
    fetch(out)                                   # compile + settle
    t0 = time.perf_counter()
    for _ in range(steps):
        out, a = chained(a, *rest)
    fetch(out)
    return (time.perf_counter() - t0) / steps * 1e3


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=0,
                    help="0 = the tuned/default bench batch")
    ap.add_argument("--steps", type=int, default=10)
    ap.add_argument("--tiny", action="store_true")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from bench import build_cfg, setup_train, time_steps, _fetch
    from dalle_pytorch_tpu.models import dalle as D
    from dalle_pytorch_tpu.ops import attention as attn_ops
    from dalle_pytorch_tpu.ops import transformer as T
    from dalle_pytorch_tpu.parallel import make_mesh
    from dalle_pytorch_tpu.utils.device import enable_compile_cache

    enable_compile_cache()

    n_dev = len(jax.devices())
    mesh = make_mesh({"dp": n_dev})

    # mirror bench_north's tuned defaults so the full-step baseline is the
    # config bench actually records (attn impl, batch, loss_chunk)
    tuned = {}
    if not args.tiny:
        try:
            with open(os.path.join(os.path.dirname(os.path.dirname(
                    os.path.abspath(__file__))), "docs",
                    "TUNE_NORTH.json")) as f:
                payload = json.load(f)
            if payload.get("backend") == jax.default_backend():
                tuned = payload.get("best", {})
        except (OSError, ValueError):
            pass
    bench_attn = tuned.get("attn") or (
        "flash" if jax.default_backend() == "tpu" else "xla")
    cfg = build_cfg(args.tiny, depth=12 if not args.tiny else 2,
                    attn_impl=bench_attn,
                    loss_chunk=tuned.get("loss_chunk") or 0,
                    heads=tuned.get("heads", 8),
                    dim_head=tuned.get("dim_head", 64),
                    remat=tuned.get("remat") or "none",
                    reversible=bool(tuned.get("reversible", False)))
    batch = args.batch or (tuned.get("batch_per_chip", 8) * n_dev
                           if not args.tiny else 4)
    key = jax.random.PRNGKey(0)
    b, n, d = batch, cfg.seq_len, cfg.dim
    h_dim = cfg.heads
    dh = cfg.dim_head
    dt = jnp.bfloat16
    results = {}

    def fetch(x):
        return _fetch(x if isinstance(x, jax.Array) else jax.tree.leaves(x)[0])

    def note(msg):
        # progress to stderr so a hang is localizable to a piece
        print(f"[profile] {msg}", file=sys.stderr, flush=True)

    # -- attention fwd+bwd, all impls, one layer x depth -------------------
    x = jax.random.normal(key, (b, h_dim, n, dh), dt)
    for impl in ("flash", "flash_pallas_bwd", "flash_pallas_fused", "xla"):
        if impl == "xla":
            # dense attention materializes (b,h,n,n) f32 weights. One
            # layer in isolation fits at the tuned batches (b=16 is
            # ~2.5G with the bwd's saved+grad copies — the full-model
            # OOMs in the 2026-07-31 sweep came from 12 STACKED layers
            # of saved weights, which this piece doesn't have); the
            # guard only protects pathological batches from wedging the
            # remote-compile helper.
            score_bytes = 3 * b * h_dim * n * n * 4
            if score_bytes > 10e9:
                note(f"skip attn_xla (est {score_bytes/1e9:.1f}G of score "
                     "tensors)")
                results[f"attn_xla_fwdbwd_ms_x{cfg.depth}"] = None
                continue
        note(f"attn impl={impl}")
        if impl.startswith("flash"):
            from dalle_pytorch_tpu.ops.flash_attention import flash_attention
            bwd = {"flash_pallas_bwd": "pallas",
                   "flash_pallas_fused": "pallas_fused"}.get(impl, "xla")
            att = functools.partial(
                flash_attention, causal=True, scale=d ** -0.5, bwd_impl=bwd)
        else:
            def att(q, k, v):
                w = attn_ops.dense_attention_weights(q, k, d ** -0.5, None,
                                                     True)
                return jnp.einsum("bhij,bhjd->bhid", w, v)

        # jaxlint: disable=JL004 — profiling harness: one jit per attention
        # impl under test, a handful of constructions total (the same
        # waived idiom as bench.py's per-kernel timing loops)
        fb = jax.jit(jax.grad(lambda q, k, v: att(q, k, v).astype(
            jnp.float32).sum(), argnums=(0, 1, 2)))
        ms = _time(fb, (x, x, x), args.steps, fetch)
        results[f"attn_{impl}_fwdbwd_ms_x{cfg.depth}"] = round(
            ms * cfg.depth, 2)

    # -- the non-attention layer matmuls (qkv/out/GEGLU), fwd+bwd ----------
    note("layer matmuls")
    lkey = jax.random.PRNGKey(1)
    tcfg = cfg.transformer
    lp = T.layer_init(lkey, tcfg, dtype=dt)
    xl = jax.random.normal(jax.random.fold_in(key, 1), (b, n, d), dt)

    def layer_no_attn(lp, x):
        p = lp["attn"]
        from dalle_pytorch_tpu.ops import core
        hh = core.layernorm(p["ln"], x)
        q, k, v = attn_ops.qkv_project(p, hh, tcfg.heads)
        o = attn_ops.output_tail(p, v)           # skip the attention mix
        x = x + o
        return x + T.ff_branch(lp, x, tcfg, None, False)

    fb = jax.jit(jax.grad(
        lambda lp, x: layer_no_attn(lp, x).astype(jnp.float32).sum()))
    ms = _time(fb, (lp, xl), args.steps, fetch)
    results[f"layer_matmuls_fwdbwd_ms_x{cfg.depth}"] = round(
        ms * cfg.depth, 2)

    # -- CE head: dense vs chunked, fwd+bwd --------------------------------
    params = D.dalle_init(jax.random.fold_in(key, 2), cfg, dtype=dt)
    hfull = jax.random.normal(jax.random.fold_in(key, 3), (b, n, d), dt)
    text = jax.random.randint(jax.random.fold_in(key, 4),
                              (b, cfg.text_seq_len), 0,
                              cfg.num_text_tokens)
    img = jax.random.randint(jax.random.fold_in(key, 5),
                             (b, cfg.image_seq_len), 0,
                             cfg.num_image_tokens)
    import dataclasses
    chunk = cfg.loss_chunk or 256
    for name, c in (("dense", dataclasses.replace(cfg, loss_chunk=0)),
                    (f"chunk{chunk}",
                     dataclasses.replace(cfg, loss_chunk=chunk))):
        note(f"ce head {name}")
        # jaxlint: disable=JL004 — profiling harness: one jit per CE-head
        # variant (dense vs chunked), two constructions total
        fb = jax.jit(jax.grad(lambda hh, c=c: D.ce_from_hidden(
            params, hh, text, img, cfg=c)))
        ms = _time(fb, (hfull,), args.steps, fetch)
        results[f"ce_head_{name}_fwdbwd_ms"] = round(ms, 2)

    # -- embeddings ---------------------------------------------------------
    note("embeddings")
    emb = jax.jit(lambda t, i: D.embed_prompt(params, cfg, t, i))
    results["embed_fwd_ms"] = round(
        _time(emb, (text, img), args.steps, fetch), 2)

    # -- adam update over the full param tree ------------------------------
    opt = optax.adam(1e-4)
    opt_state = jax.jit(opt.init)(params)
    grads = jax.tree.map(jnp.ones_like, params)

    @jax.jit
    def adam_step(params, opt_state, grads):
        upd, opt_state = opt.update(grads, opt_state, params)
        return optax.apply_updates(params, upd), opt_state

    note("adam update")
    ms = _time(lambda p, s: adam_step(p, s, grads),
               (params, opt_state), args.steps, fetch)
    results["adam_update_ms"] = round(ms, 2)

    # -- the real full step for comparison ---------------------------------
    note("full step")
    step, p2, s2, data, k2 = setup_train(cfg, batch, mesh)
    dt_s, _, _ = time_steps(step, p2, s2, data, k2, 2, args.steps)
    results["full_step_ms"] = round(dt_s / args.steps * 1e3, 2)
    # account with the attention impl and CE head the full step ACTUALLY
    # ran, so the residual is fusion/dispatch/data movement, not impl gaps
    ce_key = ("ce_head_dense_fwdbwd_ms" if not cfg.loss_chunk
              else f"ce_head_chunk{chunk}_fwdbwd_ms")
    # the tuned name 'flash_pallas' is recorded by the impl loop as
    # 'flash_pallas_bwd' (same flash-fwd + Pallas-bwd pairing build_cfg
    # resolves)
    attn_key = ("flash_pallas_bwd" if bench_attn == "flash_pallas"
                else bench_attn)
    parts = (results[f"attn_{attn_key}_fwdbwd_ms_x{cfg.depth}"],
             results[f"layer_matmuls_fwdbwd_ms_x{cfg.depth}"],
             results[ce_key],
             results["embed_fwd_ms"], results["adam_update_ms"])
    results["accounted_ms"] = (round(sum(parts), 2)
                               if None not in parts else None)
    results["full_step_attn"] = bench_attn
    results["full_step_loss_chunk"] = cfg.loss_chunk
    results["batch"] = batch
    results["backend"] = jax.default_backend()
    print(json.dumps(results), flush=True)


if __name__ == "__main__":
    main()

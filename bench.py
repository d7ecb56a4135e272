"""Benchmark harness — prints ONE JSON line for the driver.

Default run (``--config all``) measures every BASELINE.json config and emits
a single combined JSON object: the top-level fields are the north-star
metric (config 2/5 scaled down to the local chip count), and ``configs``
holds the DiscreteVAE (1), reversible+rerank (3), depth-64 block-sparse (4)
numbers plus a beyond-reference MoE-FF throughput config and an on-device
Pallas-kernel parity smoke:

  * ``value`` — steady-state train tokens/sec/chip (tokens / sec / devices
    actually participating in the sharded step);
  * ``mfu`` — measured model FLOP utilization against the chip's bf16 peak
    (analytic fwd+bwd matmul+attention FLOP count, not an estimate). The
    harness REFUSES to emit an MFU outside (0, 1) — that would mean the
    timing sync is broken, not that the chip is fast;
  * ``gen_p50_ms`` / ``gen_ms_per_token`` — p50 latency of the jit-compiled
    KV-cache sampler (full 256-token prompt -> 1024 image tokens);
  * ``vs_baseline`` — value / 2.9e5, an estimated A100 throughput for the
    same model (~430 MFLOPs/token at 40% MFU of 312 bf16 TFLOPs; the
    reference publishes no numbers, BASELINE.md). The >=1.5 target is the
    north star's ">= 1.5x A100 tokens/sec/chip".

Timing discipline: jax dispatch is asynchronous, so every timed region
here ends with a HOST FETCH of a value data-dependent on the full
computation (``float(loss)`` after the last step; an element of the
generated image). ``chip_smoke.py`` checks on the chip that this rule and
``jax.block_until_ready`` agree.

Attention path: ``--attn xla|flash|flash_pallas|flash_pallas_fused``
(default flash on TPU — the Pallas kernel; flash_pallas adds the split
Pallas backward, flash_pallas_fused the single-pass fused one). A kernel
that does not compile FAILS its config; nothing retries on another path.

Device discipline: a run without ``--tiny`` is a chip measurement — it
exits non-zero, printing no number, when jax finds no TPU. ``--tiny`` is a
CPU smoke of the harness (not a benchmark) and pins ``JAX_PLATFORMS=cpu``.
Peaks come from ``utils.device.chip_peaks`` keyed by ``device_kind``; an
unknown device is an error. ``--config all`` runs every config and exits
non-zero if any one errored.

Usage: python bench.py [--tiny] [--config all|north|vae|rev|sparse|moe|kernels]
                       [--attn xla|flash|flash_pallas|flash_pallas_fused]
                       [--steps N] [--batch B]
"""

import argparse
import json
import os
import statistics
import sys
import time
import traceback

A100_TOKENS_PER_SEC_EST = 2.9e5
A100_BF16_PEAK = 312e12     # A100 dense bf16 TFLOPs (baseline estimates)
A100_MFU_EST = 0.40         # assumed A100 training MFU for the estimates


def _emit(obj, code=0):
    print(json.dumps(obj), flush=True)
    sys.exit(code)


def _progress(msg: str) -> None:
    """Stderr progress note — stdout stays one JSON line for the driver."""
    print(f"[bench +{time.perf_counter() - _T0:7.1f}s] {msg}",
          file=sys.stderr, flush=True)


_T0 = time.perf_counter()


# Shared by the measurement scripts (tune_north, longctx_probe): classify a
# compile/run failure so sweep records read as "didn't fit" vs "broke". One
# marker list — a new message form lands everywhere at once.
OOM_MARKERS = ("RESOURCE_EXHAUSTED", "Allocation type", "exceeds the limit",
               "out of memory")


def classify_error_kind(msg: str) -> str:
    return "oom" if any(m in msg for m in OOM_MARKERS) else "error"


def merge_keyed_records(prev_payload, results, key_fn, backend="tpu"):
    """Latest-wins merge of per-point ``results`` into a previously
    committed payload's ``results`` list, keyed by ``key_fn``. A payload
    from a different backend is discarded wholesale (CPU smoke numbers
    must never sit beside chip numbers). Returns the merged record list;
    payload assembly (best/sort/extra fields) stays with the caller."""
    merged = {}
    if isinstance(prev_payload, dict) and prev_payload.get(
            "backend") == backend:
        merged = {key_fn(r): r for r in prev_payload.get("results", [])}
    merged.update({key_fn(r): r for r in results})       # latest wins
    return list(merged.values())


def atomic_write_json(path: str, obj) -> str:
    """tmp-write + os.replace — the measurement scripts call this on the
    per-point hot path and can be killed at any moment (a call's time
    limit); a truncated file would silently wipe the banked record,
    since every reader treats a JSON error as 'no payload'."""
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(obj, f, indent=2)
    os.replace(tmp, path)
    return path


def _load_tune_north():
    """Parsed docs/TUNE_NORTH.json payload (bench_north's tuned
    defaults), or None."""
    try:
        with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                               "docs", "TUNE_NORTH.json")) as f:
            return json.load(f)
    except (OSError, ValueError):
        return None


def _peaks() -> dict:
    """Published peaks of the chip this process runs on
    (``utils.device.chip_peaks`` — keyed by ``device_kind``, with their
    source; an unknown device raises instead of borrowing v5e's)."""
    import jax

    from dalle_pytorch_tpu.utils.device import chip_peaks
    return chip_peaks(jax.devices()[0].device_kind)


def _bf16_peak():
    return _peaks()["bf16_flops"]


def _hbm_bw():
    return _peaks()["hbm_bytes_per_s"]


def _fetch(x) -> float:
    """Host round-trip on one element of ``x`` — the timed regions' sync
    (module docstring). The element is data-dependent on the whole
    program that produced ``x``, so fetching it forces completion."""
    import numpy as np
    return float(np.asarray(x.reshape(-1)[:1])[0])


# ---------------------------------------------------------------------------
# analytic FLOP counts (fwd+bwd = 3x fwd matmul FLOPs)
# ---------------------------------------------------------------------------

def dalle_train_flops_per_token(cfg) -> float:
    """Matmul + attention FLOPs per sequence token for one fwd+bwd step.

    Sparse-pattern aware (conservatively): attention FLOPs are counted
    ONLY on dense layers — sparse layers' windowed/block attention is
    treated as free, so the A100 baseline estimate derived from this
    count is as FAST as the real reference could plausibly be, and the
    resulting ``vs_baseline`` never flatters this repo."""
    d, L, n = cfg.dim, cfg.depth, cfg.seq_len
    dh = cfg.heads * cfg.dim_head
    hidden = d * 4                                  # GEGLU ff_mult default
    per_layer = 2 * (d * 3 * dh + dh * d            # qkv + out proj
                     + d * hidden * 2 + hidden * d)  # GEGLU w1 (x2) + w2
    attn = 2 * (2 * n * dh)                          # qk^T + av, per token
    try:                      # DALLEConfig carries it via .transformer
        pattern = cfg.transformer.sparse_pattern
    except AttributeError:
        pattern = getattr(cfg, "sparse_pattern", (False,) * L)
    dense_layers = sum(1 for s in pattern if not s)
    logits = 2 * d * cfg.total_tokens
    embed = 0                                        # gather, not matmul
    fwd = L * per_layer + dense_layers * attn + logits + embed
    return 3.0 * fwd                                 # fwd + 2x bwd


def a100_tokens_per_sec_est(cfg) -> float:
    """Estimated A100 tokens/sec/chip for the SAME model: analytic
    fwd+bwd FLOPs at 40% MFU of A100's 312 bf16 TFLOPs — the methodology
    behind A100_TOKENS_PER_SEC_EST (2.9e5 = this formula on the north
    config), generalized so every train config gets a vs_baseline
    (VERDICT r4 item 8). The reference publishes no numbers
    (BASELINE.md), so an analytic estimate is the only available bar."""
    return A100_MFU_EST * A100_BF16_PEAK / dalle_train_flops_per_token(cfg)


def vae_train_flops_per_image(cfg) -> float:
    """Analytic conv-matmul FLOPs per image for one DiscreteVAE fwd+bwd
    step (models/vae.py structure: n stride-2 4x4 enc convs, 1x1 logits
    head, codebook mix, mirrored transpose decoder, 1x1 out). A conv is
    2 * out_pixels * k^2 * cin * cout FLOPs; a stride-2 transpose conv
    touches each INPUT pixel k^2 * cout times. Resnet blocks add two 3x3
    and one 1x1 at constant resolution."""
    n, h, c = cfg.num_layers, cfg.hidden_dim, cfg.channels
    s = cfg.image_size
    fwd = 0.0
    # encoder: stride-2 4x4 convs, cin -> cout at halved resolution
    enc_chans = [c] + [h] * n
    res = s
    for cin, cout in zip(enc_chans[:-1], enc_chans[1:]):
        res //= 2
        fwd += 2 * res * res * 16 * cin * cout
    grid = cfg.grid_size
    fwd += 2 * grid * grid * enc_chans[-1] * cfg.num_tokens   # 1x1 logits
    fwd += 2 * grid * grid * cfg.num_tokens * cfg.codebook_dim  # mix
    # resnet blocks (enc + dec): two 3x3 + one 1x1 at constant res
    res_flops = 2 * grid * grid * (9 + 9 + 1) * h * h
    fwd += 2 * cfg.num_resnet_blocks * res_flops
    # decoder: mirrored stride-2 4x4 transpose convs
    dec_in = h if cfg.num_resnet_blocks else cfg.codebook_dim
    if cfg.num_resnet_blocks:
        fwd += 2 * grid * grid * cfg.codebook_dim * h         # 1x1 stem
    dec_chans = [dec_in] + [h] * (n - 1)
    res = grid
    for cin in dec_chans:
        fwd += 2 * res * res * 16 * cin * h
        res *= 2
    fwd += 2 * s * s * h * c                                  # 1x1 out
    return 3.0 * fwd                                          # fwd + 2x bwd


def a100_images_per_sec_est(cfg) -> float:
    """A100 images/sec estimate for the VAE config — same methodology as
    a100_tokens_per_sec_est (analytic FLOPs at 40% MFU of 312 TFLOPs)."""
    return A100_MFU_EST * A100_BF16_PEAK / vae_train_flops_per_image(cfg)


def decode_roofline_ms_per_token(cfg, quantize: str = "none",
                                 batch: int = 1,
                                 hbm_bytes_per_s: float = 0.0) -> float:
    """HBM-bandwidth floor for one KV-cache decode step: every step
    re-reads the full matmul weight set (the transformer linears + the
    vocab head — the embedding tables are gathers reading one row each,
    so they are NOT streamed and don't count) and each sequence's KV
    cache; at small batch the matmuls are matrix-vector, so bytes — not
    FLOPs — bound the step. This finishes the ops/quant.py arithmetic
    (VERDICT r4 item 8): the measured gen_ms_per_token should be judged
    against THIS number, and int8 weights halve only the weight-bytes
    share. ``batch`` scales the per-sequence KV reads (weights amortize
    across the batch within one step). ``hbm_bytes_per_s`` defaults to
    the published bandwidth of the chip this process runs on."""
    d, L = cfg.dim, cfg.depth
    dh = cfg.heads * cfg.dim_head
    hidden = d * 4
    per_layer = d * 3 * dh + dh * d + d * hidden * 2 + hidden * d \
        + 4 * d                                     # qkv,out,GEGLU,2 LN
    head = d * cfg.total_tokens
    wbytes = 1 if quantize in ("int8", "int8_kv") else 2
    kvbytes = 1 if quantize == "int8_kv" else 2      # int8 cache rows
    weight_bytes = (L * per_layer + head) * wbytes
    kv_bytes = batch * 2 * L * cfg.seq_len * dh * kvbytes
    if quantize == "int8_kv":
        # each int8 row reads its f32 per-row scale too — one scale per
        # (layer, batch, HEAD, position) for K and for V (decode.init_cache
        # scale shape), so heads multiplies the count
        kv_bytes += batch * 2 * L * cfg.seq_len * cfg.heads * 4
    return (weight_bytes + kv_bytes) / (hbm_bytes_per_s or _hbm_bw()) * 1e3


# ---------------------------------------------------------------------------
# model setup
# ---------------------------------------------------------------------------

def build_cfg(tiny: bool, depth: int = 12, reversible: bool = False,
              sparse: bool = False, attn_impl: str = "xla",
              loss_chunk: int = 0, heads: int = 8, dim_head: int = 64,
              remat: str = "none", flash_block_q: int = 128,
              flash_block_k: int = 128):
    """``heads``/``dim_head`` keep heads*dim_head = 512 (the north config
    fixes dim and depth, not the head split — BASELINE.md); dim_head 128
    fills the MXU's 128-wide contraction in attention, dim_head 64 is the
    reference default. ``remat='full'`` checkpoints the scanned layer body
    (jax.checkpoint): the 2026-07-31 sweep showed per-layer saved
    activations are what cap the batch on one v5e chip (every batch>=32
    config OOM'd at compile), so remat is the lever that buys batch."""
    import jax.numpy as jnp  # noqa: F401  (jax must be importable here)
    from dalle_pytorch_tpu.models import dalle as D
    from dalle_pytorch_tpu.models import vae as V

    # unknown strings would otherwise silently run un-rematerialized under
    # a wrong label (the transformer validates too; fail before compiling)
    if remat not in ("none", "save_ln", "dots", "full"):
        raise ValueError(f"remat must be 'none', 'save_ln', 'dots' or "
                         f"'full', got {remat!r}")

    # 'flash_pallas' = flash forward + the split Pallas backward kernels;
    # 'flash_pallas_fused' = flash forward + the single-pass fused bwd
    attn_bwd = "xla"
    if attn_impl == "flash_pallas":
        attn_impl, attn_bwd = "flash", "pallas"
    elif attn_impl == "flash_pallas_fused":
        attn_impl, attn_bwd = "flash", "pallas_fused"

    if tiny:
        vcfg = V.VAEConfig(image_size=16, num_tokens=32, codebook_dim=32,
                           num_layers=2, hidden_dim=8)
        return D.DALLEConfig(
            dim=32, depth=2, vae=vcfg, num_text_tokens=64, text_seq_len=8,
            heads=2, dim_head=16, reversible=reversible,
            sparse_attn=(True, False) if sparse else False,
            attn_impl=attn_impl, attn_bwd_impl=attn_bwd,
            sparse_impl="pallas" if sparse else "ref",
            loss_chunk=loss_chunk, remat=remat)
    vcfg = V.VAEConfig(image_size=256, num_tokens=2048, codebook_dim=512,
                       num_layers=3, hidden_dim=64)
    return D.DALLEConfig(
        dim=512, depth=depth, vae=vcfg, num_text_tokens=10000,
        text_seq_len=256, reversible=reversible, heads=heads,
        dim_head=dim_head,
        sparse_attn=(True, False) * (depth // 2) if sparse else False,
        attn_impl=attn_impl, attn_bwd_impl=attn_bwd,
        flash_block_q=flash_block_q, flash_block_k=flash_block_k,
        sparse_impl="pallas" if sparse else "ref",
        loss_chunk=loss_chunk, remat=remat)


def setup_train(cfg, batch, mesh):
    import jax
    import jax.numpy as jnp
    import optax

    from dalle_pytorch_tpu.models import dalle as D
    from dalle_pytorch_tpu.parallel import shard_batch
    from dalle_pytorch_tpu.parallel.train import (dalle_loss_fn,
                                                  make_train_step,
                                                  setup_sharded)

    key = jax.random.PRNGKey(0)
    params = D.dalle_init(key, cfg, dtype=jnp.bfloat16)
    opt = optax.adam(1e-4)
    params, opt_state = setup_sharded(params, opt, mesh)
    step = make_train_step(dalle_loss_fn(cfg), opt)
    data = shard_batch(mesh, {
        "text": jax.random.randint(jax.random.fold_in(key, 1),
                                   (batch, cfg.text_seq_len), 0,
                                   cfg.num_text_tokens),
        "image": jax.random.randint(jax.random.fold_in(key, 2),
                                    (batch, cfg.image_seq_len), 0,
                                    cfg.num_image_tokens),
    })
    return step, params, opt_state, data, key


def time_steps(step, params, opt_state, data, key, warmup, steps):
    """Wall time for ``steps`` chained train steps, host-synced.

    The timed region dispatches every step and then FETCHES the last loss:
    each loss depends on the previous step's params, so the fetch cannot
    complete before all ``steps`` executions have."""
    import jax
    for i in range(max(warmup, 1)):
        params, opt_state, loss = step(params, opt_state, data,
                                       jax.random.fold_in(key, i))
    _fetch(loss)                              # drain warmup before timing
    t0 = time.perf_counter()
    for i in range(steps):
        params, opt_state, loss = step(params, opt_state, data,
                                       jax.random.fold_in(key, 100 + i))
    loss_val = _fetch(loss)                   # host sync INSIDE the region
    return time.perf_counter() - t0, loss_val, params


# ---------------------------------------------------------------------------
# configs
# ---------------------------------------------------------------------------

def bench_north(args):
    import jax

    from dalle_pytorch_tpu.parallel import make_mesh

    n_dev = len(jax.devices())
    mesh = make_mesh({"dp": n_dev})

    # tuned defaults from the last committed scripts/tune_north.py sweep
    # (docs/TUNE_NORTH.json); explicit flags always win, and the file only
    # applies on the backend it was measured on
    tuned = {}
    if not args.tiny:
        payload = _load_tune_north()
        if payload and payload.get("backend") == jax.default_backend():
            tuned = payload.get("best", {})
    batch = args.batch or (tuned.get("batch_per_chip", 8) * n_dev
                           if not args.tiny else 4)
    loss_chunk = args.loss_chunk
    if loss_chunk is None:
        loss_chunk = tuned.get("loss_chunk") or 0

    attn = args.attn
    if attn == "auto":
        attn = tuned.get("attn") or (
            "flash" if jax.default_backend() == "tpu" else "xla")
    remat = args.remat
    if remat is None:
        remat = tuned.get("remat") or "none"
    reversible = bool(tuned.get("reversible", False))
    if reversible and args.remat in ("save_ln", "dots", "full"):
        # explicit flags win: the reversible engine ignores cfg.remat
        # (transformer.py reversible branch), so honoring an explicit
        # remat request means dropping the tuned engine choice
        reversible = False
    cfg = build_cfg(args.tiny, depth=12 if not args.tiny else 2,
                    attn_impl=attn, loss_chunk=loss_chunk,
                    heads=tuned.get("heads", 8),
                    dim_head=tuned.get("dim_head", 64), remat=remat,
                    reversible=reversible,
                    flash_block_q=tuned.get("flash_block_q", 128),
                    flash_block_k=tuned.get("flash_block_k", 128))
    _progress(f"north: compiling train step (attn={attn}, batch={batch})")
    step, params, opt_state, data, key = setup_train(cfg, batch, mesh)
    dt, loss, params = time_steps(step, params, opt_state, data, key,
                                  args.warmup, args.steps)

    tokens = args.steps * batch * cfg.seq_len
    tps_chip = tokens / dt / n_dev            # all n_dev participate (dp)
    flops_tok = dalle_train_flops_per_token(cfg)
    mfu = (tps_chip * flops_tok) / _bf16_peak() \
        if jax.default_backend() == "tpu" else None
    if mfu is not None and not 0.0 < mfu < 1.0:
        raise RuntimeError(
            f"implausible measurement: mfu={mfu:.4f} "
            f"({tps_chip:.0f} tokens/sec/chip) — timing sync broken, "
            "refusing to emit (VERDICT r2 guard)")

    gen_p50 = gen_ms_tok = None
    gen_q_p50 = gen_q_ms_tok = None
    gen_extra = {}
    if not args.no_gen:
        variants = [("", params, False)]
        if args.gen_quant:
            # same sampler, int8-quantized linears + vocab head — the
            # weight-HBM quarter of the per-token cost (ops/quant.py) —
            # and the full-int8 variant with the KV cache int8 too
            # (per-row scales, ops/decode.py)
            from dalle_pytorch_tpu.models.dalle import quantize_for_decode
            qparams = quantize_for_decode(params)
            variants.append(("int8_", qparams, False))
            variants.append(("int8kv_", qparams, True))
        for prefix, ps, qc in variants:
            for i, b in enumerate(args.gen_batches):
                p50, ms_tok = bench_generate(cfg, ps, args, batch=b,
                                             quantize_cache=qc)
                if i == 0 and not prefix:
                    gen_p50, gen_ms_tok = p50, ms_tok
                elif i == 0 and prefix == "int8_":
                    gen_q_p50, gen_q_ms_tok = p50, ms_tok
                elif i == 0:
                    gen_extra["gen_int8kv_p50_ms"] = p50
                    gen_extra["gen_int8kv_ms_per_token"] = ms_tok
                else:
                    # self-describing throughput: ms_tok is wall-ms per
                    # DECODE STEP (all b sequences advance together), so
                    # tokens/sec = b * 1000 / ms_tok
                    gen_extra[f"gen_{prefix}b{b}_p50_ms"] = p50
                    gen_extra[f"gen_{prefix}b{b}_tokens_per_sec"] = round(
                        b * 1000.0 / ms_tok, 1)

    out = {
        "metric": ("DALLE train tokens/sec/chip (depth-12 dim-512, seq "
                   "1280, bf16, attn=%s)" % attn) if not args.tiny
                  else "tiny smoke tokens/sec/chip",
        "value": round(tps_chip, 1),
        "unit": "tokens/sec/chip",
        "vs_baseline": round(tps_chip / A100_TOKENS_PER_SEC_EST, 3),
        "devices": n_dev,
        "batch": batch,
        "loss": round(loss, 4),
        "mfu": round(mfu, 4) if mfu is not None else None,
        "remat": cfg.remat,
        "reversible": cfg.reversible,
        "gen_p50_ms": gen_p50,
        "gen_ms_per_token": gen_ms_tok,
        "backend": jax.default_backend(),
    }
    if gen_ms_tok is not None and args.gen_batches[0] != 1:
        # headline gen_* fields are historically batch-1; mark a deviation
        # so records stay comparable
        out["gen_batch"] = args.gen_batches[0]
    if gen_ms_tok is not None and jax.default_backend() == "tpu":
        # judge the decode against its HBM-bandwidth floor (the per-token
        # cost is weight+cache reads, not FLOPs — see the roofline fn);
        # the floor is computed at the HEADLINE batch so the two sides of
        # the fraction describe the same program
        gb = args.gen_batches[0]
        floor = decode_roofline_ms_per_token(cfg, batch=gb)
        out["gen_roofline_ms_per_token"] = round(floor, 4)
        out["gen_roofline_frac"] = round(floor / gen_ms_tok, 3)
        # prefill/decode split (VERDICT r4 weak 8): the fixed prompt cost
        # vs everything after it. The prefill program uses the SAME
        # settings the headline generate_images ran (no prompt mask,
        # fp KV cache — ADVICE r5 #1), and the residual is named
        # gen_NONPREFILL: it folds in sampling + the VAE decode, so it is
        # an upper bound on pure decode, not a decode measurement.
        prefill_ms = bench_prefill(cfg, params, args, batch=gb,
                                   prompt_mask=None, quantize_cache=False)
        n_gen_toks = cfg.seq_len - cfg.text_seq_len
        out["gen_prefill_ms"] = prefill_ms
        out["gen_nonprefill_ms_per_token"] = round(
            max(gen_p50 - prefill_ms, 0.0) / n_gen_toks, 3)
    if gen_q_ms_tok is not None:
        out["gen_int8_p50_ms"] = gen_q_p50
        out["gen_int8_ms_per_token"] = gen_q_ms_tok
        if jax.default_backend() == "tpu":
            q_floor = decode_roofline_ms_per_token(
                cfg, quantize="int8", batch=args.gen_batches[0])
            out["gen_int8_roofline_ms_per_token"] = round(q_floor, 4)
            out["gen_int8_roofline_frac"] = round(q_floor / gen_q_ms_tok, 3)
        kv_ms = gen_extra.get("gen_int8kv_ms_per_token")
        if kv_ms and jax.default_backend() == "tpu":
            kv_floor = decode_roofline_ms_per_token(
                cfg, quantize="int8_kv", batch=args.gen_batches[0])
            gen_extra["gen_int8kv_roofline_ms_per_token"] = round(
                kv_floor, 4)
            gen_extra["gen_int8kv_roofline_frac"] = round(kv_floor / kv_ms,
                                                          3)
    out.update(gen_extra)
    return out


def bench_generate(cfg, params, args, clip_bundle=None, reps=None,
                   batch: int = 1, quantize_cache: bool = False):
    """(p50 ms, ms/token) of the jit-compiled KV-cache sampler, full-length
    prompt. The whole sampler (prefill + lax.scan decode + VAE decode) is
    ONE jit program — not the eager dispatch VERDICT r2 item 4 flagged.
    ``batch`` > 1 samples that many prompts in one program (the reference's
    per-token full re-forward cannot amortize a batch; the scan does —
    ms/token here is per-sequence wall time / tokens, so throughput in
    tokens/sec is batch * 1000 / ms_per_token)."""
    import functools

    import jax
    import jax.numpy as jnp

    from dalle_pytorch_tpu.models import dalle as D
    from dalle_pytorch_tpu.models import vae as V

    key = jax.random.PRNGKey(1)
    vae_params = V.vae_init(key, cfg.vae, dtype=jnp.bfloat16)
    text = jax.random.randint(key, (batch, cfg.text_seq_len), 0,
                              cfg.num_text_tokens)
    n_gen = cfg.seq_len - cfg.text_seq_len    # image tokens generated

    if clip_bundle is not None:
        clip_params, clip_cfg = clip_bundle

        @jax.jit
        def gen(params, vae_params, clip_params, text, rng):
            return D.generate_images(params, vae_params, text, cfg=cfg,
                                     rng=rng, clip_params=clip_params,
                                     clip_cfg=clip_cfg,
                                     quantize_cache=quantize_cache)

        run = functools.partial(gen, params, vae_params, clip_params, text)

        def sync(out):
            # fetch the SCORES: they depend on both the sampler and the
            # CLIP forward, so the rerank compute stays inside the timing
            return _fetch(out[1])             # (images, scores)
    else:

        @jax.jit
        def gen(params, vae_params, text, rng):
            return D.generate_images(params, vae_params, text, cfg=cfg,
                                     rng=rng,
                                     quantize_cache=quantize_cache)

        run = functools.partial(gen, params, vae_params, text)
        sync = _fetch

    _progress("gen: compiling sampler"
              + (" (rerank)" if clip_bundle is not None else ""))
    sync(run(jax.random.fold_in(key, 0)))     # compile + first run
    times = []
    for i in range(reps or args.gen_reps):
        t0 = time.perf_counter()
        sync(run(jax.random.fold_in(key, 1 + i)))
        times.append((time.perf_counter() - t0) * 1e3)
    p50 = statistics.median(times)
    return round(p50, 1), round(p50 / n_gen, 3)


def bench_prefill(cfg, params, args, batch: int = 1, prompt_mask=None,
                  quantize_cache: bool = False):
    """p50 ms of the PREFILL half alone (prompt embed + batched pass +
    cache fill) — separates the sampler's fixed prompt cost from the rest
    (VERDICT r4 weak item 8: no committed number separated the two).

    ``prompt_mask``/``quantize_cache`` MUST mirror what the
    ``generate_images`` call being decomposed used, or the subtraction
    compares two different prefill programs (ADVICE r5 #1); bench_north
    passes the headline sampler's settings explicitly. The residual of
    gen_p50_ms beyond this (emitted as gen_nonprefill_ms_per_token) is
    the 1024-step decode scan + sampling + VAE decode — an upper bound
    on, not a measurement of, pure decode cost."""
    import functools

    import jax
    import jax.numpy as jnp

    from dalle_pytorch_tpu.models import dalle as D
    from dalle_pytorch_tpu.ops import decode as decode_ops

    key = jax.random.PRNGKey(1)
    text = jax.random.randint(key, (batch, cfg.text_seq_len), 0,
                              cfg.num_text_tokens)

    @jax.jit
    def pre(params, text):
        tokens = D.embed_prompt(params, cfg, text)
        h, cache = decode_ops.prefill(params["transformer"], tokens,
                                      cfg=cfg.transformer,
                                      total_len=cfg.seq_len,
                                      prompt_mask=prompt_mask,
                                      quantize_cache=quantize_cache)
        return h, cache

    run = functools.partial(pre, params, text)
    _progress("gen: compiling prefill-only program")
    _fetch(run()[0])                          # compile + first run
    times = []
    for i in range(reps_ := max(2, args.gen_reps)):
        t0 = time.perf_counter()
        _fetch(run()[0])
        times.append((time.perf_counter() - t0) * 1e3)
    return round(statistics.median(times), 1)


def bench_vae(args):
    """BASELINE config 1: DiscreteVAE 256px/3-layer recon train step."""
    import jax
    import jax.numpy as jnp
    import optax

    from dalle_pytorch_tpu.models import vae as V
    from dalle_pytorch_tpu.parallel import make_mesh, shard_batch
    from dalle_pytorch_tpu.parallel.train import (make_train_step,
                                                  setup_sharded, vae_loss_fn)

    n_dev = len(jax.devices())
    mesh = make_mesh({"dp": n_dev})
    if args.tiny:
        cfg = V.VAEConfig(image_size=16, num_tokens=32, codebook_dim=32,
                          num_layers=2, hidden_dim=8)
        batch = args.batch or 4
    else:
        cfg = V.VAEConfig(image_size=256, num_tokens=2048, codebook_dim=256,
                          num_layers=3, hidden_dim=128)
        batch = args.batch or 8 * n_dev
    key = jax.random.PRNGKey(0)
    params = V.vae_init(key, cfg, dtype=jnp.bfloat16)
    opt = optax.adam(1e-4)
    params, opt_state = setup_sharded(params, opt, mesh)
    step = make_train_step(vae_loss_fn(cfg, smooth_l1=True), opt)
    imgs = jax.random.uniform(key, (batch, cfg.image_size, cfg.image_size,
                                    3), jnp.bfloat16, -1, 1)
    data = shard_batch(mesh, {"images": imgs})
    _progress("vae: compiling train step")
    dt, loss, _ = time_steps(step, params, opt_state, data, key,
                             args.warmup, args.steps)
    ips = args.steps * batch / dt / n_dev
    return {
        "metric": "DiscreteVAE train images/sec/chip (256px, 3-layer, 2048 "
                  "tokens)" if not args.tiny else "tiny vae images/sec/chip",
        "value": round(ips, 2), "unit": "images/sec/chip",
        # same methodology as the north number: analytic fwd+bwd FLOPs at
        # an assumed 40% MFU on A100 (VERDICT r4 item 8 — no more nulls)
        "vs_baseline": round(ips / a100_images_per_sec_est(cfg), 3),
        "a100_images_per_sec_est": round(a100_images_per_sec_est(cfg), 1),
        "loss": round(loss, 4), "batch": batch,
        "devices": n_dev, "backend": jax.default_backend(),
    }


def bench_rev(args):
    """BASELINE config 3: depth-12 reversible train + CLIP-reranked
    generate_images latency."""
    import jax
    import jax.numpy as jnp

    from dalle_pytorch_tpu.models import clip as C
    from dalle_pytorch_tpu.parallel import make_mesh

    n_dev = len(jax.devices())
    mesh = make_mesh({"dp": n_dev})
    batch = args.batch or (8 * n_dev if not args.tiny else 4)
    cfg = build_cfg(args.tiny, depth=12 if not args.tiny else 2,
                    reversible=True, attn_impl=args.attn if args.attn != "auto"
                    else "xla")
    _progress("rev: compiling train step")
    step, params, opt_state, data, key = setup_train(cfg, batch, mesh)
    dt, loss, params = time_steps(step, params, opt_state, data, key,
                                  args.warmup, args.steps)
    tps_chip = args.steps * batch * cfg.seq_len / dt / n_dev

    if args.tiny:
        ccfg = C.CLIPConfig(dim_text=32, dim_image=32, dim_latent=32,
                            num_text_tokens=cfg.num_text_tokens,
                            text_seq_len=cfg.text_seq_len,
                            visual_image_size=cfg.vae.image_size,
                            text_enc_depth=1, visual_enc_depth=1,
                            text_heads=2, visual_heads=2,
                            visual_patch_size=8)
    else:
        ccfg = C.CLIPConfig(num_text_tokens=cfg.num_text_tokens,
                            text_seq_len=cfg.text_seq_len,
                            visual_image_size=cfg.vae.image_size)
    clip_params = C.clip_init(jax.random.PRNGKey(7), ccfg,
                              dtype=jnp.bfloat16)
    gen_p50, gen_ms_tok = bench_generate(cfg, params, args,
                                         clip_bundle=(clip_params, ccfg))
    return {
        "metric": "DALLE reversible train tokens/sec/chip (depth-12) + CLIP "
                  "rerank gen" if not args.tiny else "tiny reversible",
        "value": round(tps_chip, 1), "unit": "tokens/sec/chip",
        "vs_baseline": round(tps_chip / A100_TOKENS_PER_SEC_EST, 3),
        "gen_rerank_p50_ms": gen_p50, "gen_rerank_ms_per_token": gen_ms_tok,
        "loss": round(loss, 4),
        "devices": n_dev, "backend": jax.default_backend(),
    }


def bench_sparse(args):
    """BASELINE config 4: depth-64 sparse_attn=(True,False)*32 via the
    Pallas block-sparse kernel, vs the ref (einsum) sparse path."""
    import dataclasses

    import jax

    from dalle_pytorch_tpu.parallel import make_mesh

    n_dev = len(jax.devices())
    mesh = make_mesh({"dp": n_dev})
    depth = 64 if not args.tiny else 2
    # batch 1/chip: depth-64's per-layer activation stacks for bwd overflow
    # a single chip's HBM at batch 2 (remat="full" instead sends the
    # remat+cond+pallas nest into a pathological Mosaic/XLA compile)
    batch = args.batch or (n_dev if not args.tiny else 4)
    steps = max(1, args.steps // 2)           # depth-64 x2 impls: keep short
    results = {}
    for impl in ("windowed", "pallas", "ref"):
        _progress(f"sparse: compiling impl={impl}")
        cfg = dataclasses.replace(build_cfg(args.tiny, depth=depth,
                                            sparse=True), sparse_impl=impl)
        step, params, opt_state, data, key = setup_train(cfg, batch, mesh)
        dt, loss, _ = time_steps(step, params, opt_state, data, key,
                                 args.warmup, steps)
        results[impl] = steps * batch * cfg.seq_len / dt / n_dev
    return {
        "metric": "DALLE depth-64 block-sparse train tokens/sec/chip "
                  "(windowed fast path)" if not args.tiny else "tiny sparse",
        "value": round(results["windowed"], 1), "unit": "tokens/sec/chip",
        # analytic depth-64 FLOPs (attention counted on dense layers only
        # — conservative: treats the reference's DeepSpeed sparse layers
        # as free) at 40% A100 MFU, same methodology as the north number
        "vs_baseline": round(results["windowed"]
                             / a100_tokens_per_sec_est(cfg), 3),
        "a100_tokens_per_sec_est": round(a100_tokens_per_sec_est(cfg), 1),
        "windowed_vs_ref_speedup": round(
            results["windowed"] / results["ref"], 3),
        "pallas_vs_ref_speedup": round(results["pallas"] / results["ref"],
                                       3),
        "pallas_tokens_sec_chip": round(results["pallas"], 1),
        "ref_tokens_sec_chip": round(results["ref"], 1),
        "devices": n_dev, "backend": jax.default_backend(),
    }


def bench_kernels(args):
    """Kernel parity smoke (VERDICT r2 item 6): flash + block-sparse forward
    AND backward, parity-checked against the XLA einsum paths. On TPU the
    kernels go through Mosaic compilation (never the interpreter), so a
    lowering regression fails this loudly instead of hiding behind
    interpret-mode tests; off-TPU (e.g. the CI smoke) the kernels run
    interpreted — the emitted ``interpreted`` field records which one this
    result actually covers."""
    import jax
    import jax.numpy as jnp

    from dalle_pytorch_tpu.ops import core
    from dalle_pytorch_tpu.ops.attention import dense_attention_weights
    from dalle_pytorch_tpu.ops.block_sparse import block_sparse_attention
    from dalle_pytorch_tpu.ops.flash_attention import flash_attention
    from dalle_pytorch_tpu.ops.sparse import sparse_attention_ref

    b, h, n, d = (1, 2, 64, 16) if args.tiny else (2, 4, 256, 64)
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(kq, (b, h, n, d), jnp.float32)
    k = jax.random.normal(kk, (b, h, n, d), jnp.float32)
    v = jax.random.normal(kv, (b, h, n, d), jnp.float32)
    # last batch row half-padded: exercises the pad-mask kernel path
    lens = jnp.full((b, 1), n).at[-1, 0].set(n // 2)
    mask = jnp.arange(n)[None, :] < lens
    scale = d ** -0.5

    def flash(q, k, v):
        return flash_attention(q, k, v, scale=scale, causal=True, mask=mask)

    def flash_pallas_bwd(q, k, v):
        return flash_attention(q, k, v, scale=scale, causal=True, mask=mask,
                               bwd_impl="pallas")

    def flash_pallas_fused(q, k, v):
        return flash_attention(q, k, v, scale=scale, causal=True, mask=mask,
                               bwd_impl="pallas_fused")

    def dense_ref(q, k, v):
        w = dense_attention_weights(q, k, scale, mask, True)
        return jnp.einsum("bhij,bhjd->bhid", w, v)

    def bs(q, k, v):
        return block_sparse_attention(q, k, v, scale=scale, causal=True,
                                      mask=mask)

    def bs_ref(q, k, v):
        return sparse_attention_ref(q, k, v, scale=scale, causal=True,
                                    mask=mask)

    def sq_loss(f):
        return lambda q, k, v: (f(q, k, v).astype(jnp.float32) ** 2).sum()

    out = {}
    # RELATIVE error: TPU MXU matmuls round f32 operands through bf16
    # passes, so kernel-vs-XLA abs diffs sit at ~0.5% of magnitude by
    # construction (measured 0.4-0.7% rel on-chip). 2% catches real lowering
    # bugs (wrong mask, wrong tile, stale stats all blow past 100%).
    ref_grads = {}                      # each O(n^2) reference bwd runs once
    for name, fn, ref in (("flash", flash, dense_ref),
                          ("flash_pallas_bwd", flash_pallas_bwd, dense_ref),
                          ("flash_pallas_fused", flash_pallas_fused,
                           dense_ref),
                          ("block_sparse", bs, bs_ref)):
        _progress(f"kernels: compiling {name}")
        if not name.startswith("flash_pallas"):
            # bwd_impl only changes the custom_vjp backward — re-checking
            # the byte-identical forward would just pay a second compile
            # jaxlint: disable=JL004 — one compile per benched kernel,
            # by design: the loop iterates distinct fns, not repeat calls
            o = jax.jit(fn)(q, k, v)
            r = ref(q, k, v)
            out[f"{name}_fwd_reldiff"] = float(
                jnp.max(jnp.abs(o - r)) / jnp.max(jnp.abs(r)))
        # jaxlint: disable=JL004 — ditto: each iteration jits a new fn once
        g = jax.jit(jax.grad(sq_loss(fn), argnums=(0, 1, 2)))(q, k, v)
        if ref not in ref_grads:
            ref_grads[ref] = jax.grad(sq_loss(ref),
                                      argnums=(0, 1, 2))(q, k, v)
        gr = ref_grads[ref]
        out[f"{name}_grad_reldiff"] = float(
            max(jnp.max(jnp.abs(a - b_)) / jnp.max(jnp.abs(b_))
                for a, b_ in zip(g, gr)))
    out["backend"] = jax.default_backend()
    out["interpreted"] = core.pallas_interpret()
    out["parity_ok"] = all(val < 2e-2 for key, val in out.items()
                           if key.endswith("reldiff"))
    if not out["parity_ok"]:
        raise RuntimeError(f"kernel parity FAILED: {out}")

    if not out["interpreted"] and not args.tiny:
        # Isolated fwd+bwd timing at the FLAGSHIP sparse shape (seq 1280,
        # bf16, depth-64's per-layer call) — the committed artifact for
        # "does the Pallas kernel beat its XLA oracle at a stated shape".
        # Timed like every region here: chained calls, one
        # data-dependent host fetch at the end.
        ns, bs_, steps = 1280, 8, 10
        kq2, kk2, kv2 = jax.random.split(jax.random.PRNGKey(1), 3)
        q2 = jax.random.normal(kq2, (bs_, 8, ns, 64), jnp.bfloat16)
        k2 = jax.random.normal(kk2, (bs_, 8, ns, 64), jnp.bfloat16)
        v2 = jax.random.normal(kv2, (bs_, 8, ns, 64), jnp.bfloat16)

        def bs_big(q, k, v):
            return block_sparse_attention(q, k, v, scale=64 ** -0.5,
                                          causal=True)

        def bs_ref_big(q, k, v):
            return sparse_attention_ref(q, k, v, scale=64 ** -0.5,
                                        causal=True)

        from dalle_pytorch_tpu.ops.sparse import sparse_attention_windowed

        def bs_win_big(q, k, v):
            return sparse_attention_windowed(q, k, v, scale=64 ** -0.5,
                                             causal=True)

        times = {}
        for name, fn in (("pallas", bs_big), ("ref", bs_ref_big),
                         ("windowed", bs_win_big)):
            _progress(f"kernels: timing sparse {name} fwd+bwd "
                      f"@ seq {ns}")
            # jaxlint: disable=JL004 — one compile per benched kernel;
            # the timed loop below reuses this wrapper
            step = jax.jit(jax.grad(sq_loss(fn), argnums=(0, 1, 2)))
            g = step(q2, k2, v2)
            _fetch(g[0])                      # compile + warm
            t0 = time.perf_counter()
            x = q2
            for _ in range(steps):
                g = step(x, k2, v2)
                x = q2 + 0.0 * g[0].astype(q2.dtype)  # chain dependence
            _fetch(g[0])
            times[name] = (time.perf_counter() - t0) / steps * 1e3
        out["sparse_attn_ms"] = {kk_: round(tv, 3)
                                 for kk_, tv in times.items()}
        out["sparse_pallas_vs_ref_isolated"] = round(
            times["ref"] / times["pallas"], 3)
        out["sparse_pallas_vs_windowed_isolated"] = round(
            times["windowed"] / times["pallas"], 3)
    return out


def bench_moe(args):
    """Beyond-reference config: the flagship transformer with every FF
    replaced by a top-2 MoE of 8 experts (ops/moe.py), trained on a dp
    mesh. Correctness lives on the CPU mesh (tests/test_moe.py, the
    dryrun's dp x ep leg); this records the EP layer's on-chip
    throughput. No MFU is reported: dalle_train_flops_per_token counts
    the dense FF, not the k/num_experts-scaled MoE cost."""
    import dataclasses

    import jax

    from dalle_pytorch_tpu.parallel import make_mesh

    n_dev = len(jax.devices())
    mesh = make_mesh({"dp": n_dev})
    attn = args.attn
    if attn == "auto":
        attn = "flash" if jax.default_backend() == "tpu" else "xla"
    cfg = dataclasses.replace(
        build_cfg(args.tiny, depth=12 if not args.tiny else 2,
                  attn_impl=attn, loss_chunk=256 if not args.tiny else 0),
        moe_experts=8 if not args.tiny else 2)
    batch = args.batch or (8 * n_dev if not args.tiny else 4)
    steps = max(1, args.steps // 2)
    _progress("moe: compiling train step")
    step, params, opt_state, data, key = setup_train(cfg, batch, mesh)
    dt, loss, _ = time_steps(step, params, opt_state, data, key,
                             args.warmup, steps)
    tps = steps * batch * cfg.seq_len / dt / n_dev
    return {
        "metric": "DALLE MoE-FF (8 experts, top-2) train tokens/sec/chip"
                  if not args.tiny else "tiny moe",
        "value": round(tps, 1), "unit": "tokens/sec/chip",
        "vs_baseline": None, "loss": round(loss, 4),
        "moe_experts": cfg.moe_experts, "batch": batch,
        "devices": n_dev, "backend": jax.default_backend(),
    }


def _serve_load_point(engine, queue, rps, n_req, prompt_len):
    """One offered-load point: requests arrive on a deterministic
    schedule (inter-arrival = 1/rps) while the engine drains them.
    Returns the per-point record, including the host-round-trip
    accounting the device-resident loop exists to improve: device_gets
    (emit-ring harvests) per generated token and per fused decode step,
    measured over THIS point's deltas."""
    import statistics as stats_mod

    from dalle_pytorch_tpu.serve import QueueFull, Request, SamplingParams

    base = {"offered_rps": rps, "requests": n_req}
    occ0, steps0 = engine.occupancy_sum, engine.decode_steps
    tok0, harv0 = engine.tokens_decoded, engine.harvests
    completed, rejected = [], 0
    t0 = time.perf_counter()
    next_arrival, submitted = t0, 0
    pending = []
    while submitted < n_req or pending:
        now = time.perf_counter()
        while submitted < n_req and now >= next_arrival:
            try:
                pending.append(queue.submit(Request(
                    codes=(1 + submitted % 7,) * prompt_len,
                    seed=submitted, sampling=SamplingParams())))
            except QueueFull:
                rejected += 1       # structured shed — counted, typed
            submitted += 1
            next_arrival += 1.0 / rps
        engine.step_once()
        done = [h for h in pending if h.done()]
        for h in done:
            completed.append(h.result())
            pending.remove(h)
    # stop the clock at the LAST fulfillment: the post-completion
    # pipeline flush below is dead chunks only (grows with K) and must
    # not bias the K-sweep throughput comparison
    wall = time.perf_counter() - t0
    engine.run_until_idle()         # flush the in-flight chunk pipeline
    lats = sorted(r.total_s for r in completed if r.ok)
    n_ok = len(lats)
    d_tok = engine.tokens_decoded - tok0
    d_harv = engine.harvests - harv0
    d_steps = engine.decode_steps - steps0
    tokens_per_req = engine.cfg.seq_len - prompt_len
    base.update({
        "completed": n_ok, "rejected": rejected,
        "throughput_imgs_per_s": round(n_ok / wall, 3),
        "tokens_per_s": round(n_ok * tokens_per_req / wall, 1),
        "p50_latency_ms": round(1e3 * stats_mod.median(lats), 1)
        if lats else None,
        "p95_latency_ms": round(
            1e3 * lats[min(int(0.95 * n_ok), n_ok - 1)], 1)
        if lats else None,
        "wall_s": round(wall, 2),
        # the before/after of the device-resident loop: with K-step
        # chunks and >= 1 slot busy this is <= 1/K (one harvest per
        # K*occupancy tokens), vs 1/occupancy for the old per-step fetch
        "host_round_trips_per_token": round(d_harv / max(d_tok, 1), 6),
        "round_trips_per_step": round(d_harv / max(d_steps, 1), 6),
        # occupancy over THIS load point's steps, not the engine lifetime
        "mean_occupancy": round((engine.occupancy_sum - occ0)
                                / max(d_steps, 1), 3),
    })
    # per-phase attribution off the request traces (obs/trace.py): how
    # much of the p95 is QUEUE rather than decode — the number that
    # says "add a replica" vs "tune the kernel"
    qws = sorted(
        sum(s["total_s"] for s in r.trace.get("spans", ())
            if s["name"] == "queue_wait")
        for r in completed if r.ok and r.trace is not None)
    if qws:
        base["queue_wait_p50_ms"] = round(
            1e3 * qws[min(len(qws) // 2, len(qws) - 1)], 2)
        base["queue_wait_p95_ms"] = round(
            1e3 * qws[min(int(0.95 * len(qws)), len(qws) - 1)], 2)
    return base


def _serve_kv_budget_compare(params, cfg, *, num_slots, page_size,
                             min_requests=0, chunk_steps=8):
    """Dense vs paged under the SAME simulated HBM page budget — the
    number the paged KV subsystem exists for. The budget is what
    ``dense_slots`` full-length dense caches occupy (in page units);
    dense can never hold more than that many concurrent requests, while
    the paged engine spends the same pages through block tables and
    admits up to ``2 * dense_slots`` slots whose ragged positions share
    the pool (mid-run exhaustion exercises the real eviction/requeue
    path — evicted requests must still complete, token-exact by
    determinism). Records peak concurrency, ``kv_hbm_bytes``,
    ``pages_in_use_p95``, and eviction counts per mode, and ASSERTS the
    paged engine sustained strictly more concurrent requests with every
    request completing in both modes."""
    from dalle_pytorch_tpu.serve import (Request, RequestQueue,
                                         SamplingParams, kv_pool)
    from dalle_pytorch_tpu.serve.engine import Engine

    prompt_len = min(4, cfg.text_seq_len)
    pages_per_seq = kv_pool.pages_for(cfg.seq_len, page_size)
    dense_slots = max(2, num_slots // 2)
    budget_pages = dense_slots * pages_per_seq
    # enough offered load to overcommit the paged engine's slots (the
    # comparison needs the pool, not the request count, to be the
    # binding constraint); derived HERE from dense_slots so the
    # overcommit guarantee can't drift from the slot split above
    n_req = max(min_requests, 2 * dense_slots + 2)
    out = {"page_size": page_size, "pages_per_seq": pages_per_seq,
           "dense_slots": dense_slots, "paged_slots": 2 * dense_slots,
           "budget_pages": budget_pages, "requests": n_req}
    for mode in ("dense", "paged"):
        queue = RequestQueue(max_depth=max(2 * n_req, 8))
        if mode == "dense":
            engine = Engine(params, cfg, queue, num_slots=dense_slots,
                            chunk_steps=chunk_steps)
        else:
            # + 1: the reserved trash page is allocator bookkeeping, not
            # usable KV budget
            engine = Engine(params, cfg, queue, num_slots=2 * dense_slots,
                            chunk_steps=chunk_steps, kv="paged",
                            page_size=page_size,
                            num_pages=budget_pages + 1)
        handles = [queue.submit(Request(
            codes=(1 + i % 7,) * prompt_len, seed=i,
            sampling=SamplingParams())) for i in range(n_req)]
        peak = 0
        for _ in range(1_000_000):
            busy = engine.step_once()
            peak = max(peak, engine.active_slots())
            if not busy and engine.idle():
                break
        ok = sum(h.result(timeout=60).status == "ok" for h in handles)
        stats = engine.stats()
        out[mode] = {
            "num_slots": engine.num_slots,
            "completed": ok,
            "max_concurrency": peak,
            "kv_hbm_bytes": stats["kv_hbm_bytes"],
        }
        if mode == "paged":
            out[mode].update({
                "pages_in_use_p95": stats["pages_in_use_p95"],
                "pages_peak": stats["pages_peak"],
                "evicted": stats["evicted"],
                "requeued": stats["requeued"],
            })
    if out["dense"]["completed"] != n_req \
            or out["paged"]["completed"] != n_req:
        raise AssertionError(
            f"kv budget compare: not every request completed "
            f"(dense {out['dense']['completed']}/{n_req}, paged "
            f"{out['paged']['completed']}/{n_req})")
    if out["paged"]["max_concurrency"] <= out["dense"]["max_concurrency"]:
        raise AssertionError(
            f"paged engine did not sustain more concurrency than dense "
            f"under the same page budget: paged "
            f"{out['paged']['max_concurrency']} vs dense "
            f"{out['dense']['max_concurrency']}")
    return out


def _serve_paged_attn_compare(params, cfg, *, num_slots, page_size,
                              chunk_steps=8):
    """Gather vs kernel over the same paged pool and burst — the number
    the ragged paged-attention kernel exists for: per-token KV read
    traffic down, so ms/token down. Both legs run the identical
    fully-provisioned fused-K paged engine; each leg records measured
    ms/token (warmed, compile excluded) plus the analytic KV
    read-bytes-per-token model
    (``ops.paged_attention.modeled_kv_read_bytes_per_token`` — the
    gather leg reads the full ``seq_len`` view every step, the kernel
    leg only the live pages; HBM counters are not host-observable, so
    bytes are modeled, time is measured). The kernel-beats-gather
    ms/token assertion fires on REAL TPU only: on CPU the kernel runs
    under the Pallas interpreter, whose emulation overhead is not the
    hardware's — there the record is report-only (``asserted``:false),
    which is what CI's serve-perf kernel leg runs. Leg-to-leg token
    agreement is recorded (``token_mismatches``); the byte-identical
    contract itself is pinned in f32 by tests/test_paged_attention.py
    (bench runs bf16 params, where the kernel's f32 accumulation is
    deliberately not bit-matched to the gather's bf16 scores)."""
    import numpy as np

    from dalle_pytorch_tpu.ops import paged_attention as PA
    from dalle_pytorch_tpu.serve import Request, RequestQueue, \
        SamplingParams
    from dalle_pytorch_tpu.serve.engine import Engine

    import jax
    import jax.numpy as jnp

    prompt_len = min(4, cfg.text_seq_len)
    n_req = 2 * num_slots
    tokens_per_req = cfg.seq_len - prompt_len
    on_tpu = jax.default_backend() == "tpu"
    tcfg = cfg.transformer
    itemsize = jnp.dtype(params["text_emb"]["w"].dtype).itemsize
    out = {"page_size": page_size, "chunk_steps": chunk_steps,
           "requests": n_req, "asserted": on_tpu}
    toks = {}
    for impl in ("gather", "kernel"):
        queue = RequestQueue(max_depth=2 * n_req + 4)
        engine = Engine(params, cfg, queue, num_slots=num_slots,
                        chunk_steps=chunk_steps, kv="paged",
                        page_size=page_size, paged_attn=impl)
        # warm the decode program + prefill bucket outside the timing
        h = queue.submit(Request(codes=(1,) * prompt_len, seed=0,
                                 sampling=SamplingParams()))
        engine.run_until_idle()
        h.result(timeout=120)
        t0 = time.perf_counter()
        handles = [queue.submit(Request(
            codes=(1 + i % 7,) * prompt_len, seed=i,
            sampling=SamplingParams())) for i in range(n_req)]
        engine.run_until_idle()
        wall = time.perf_counter() - t0
        results = [h.result(timeout=120) for h in handles]
        ok = sum(r.status == "ok" for r in results)
        if ok != n_req:
            raise AssertionError(
                f"paged_attn={impl}: only {ok}/{n_req} completed")
        snap = engine.stats()
        if snap["decode_compiles"] != 1:
            raise AssertionError(
                f"paged_attn={impl}: decode compiled "
                f"{snap['decode_compiles']} times — the kernel must live "
                f"inside the ONE fused decode program")
        toks[impl] = [np.asarray(r.tokens) for r in results]
        out[impl] = {
            "wall_s": round(wall, 4),
            "ms_per_token": round(
                1e3 * wall / (n_req * tokens_per_req), 4),
            "read_bytes_per_token": int(
                PA.modeled_kv_read_bytes_per_token(
                    depth=tcfg.depth, heads=tcfg.heads,
                    dim_head=tcfg.dim_head, total_len=cfg.seq_len,
                    page_size=page_size, prompt_len=prompt_len,
                    itemsize=itemsize, impl=impl)),
            "decode_compiles": snap["decode_compiles"],
        }
    out["read_bytes_ratio"] = round(
        out["gather"]["read_bytes_per_token"]
        / max(out["kernel"]["read_bytes_per_token"], 1), 2)
    out["token_mismatches"] = int(sum(
        not np.array_equal(a, b)
        for a, b in zip(toks["gather"], toks["kernel"])))
    if on_tpu and out["kernel"]["ms_per_token"] \
            >= out["gather"]["ms_per_token"]:
        raise AssertionError(
            f"ragged paged-attention kernel did not beat the dense-view "
            f"gather on hardware: {out['kernel']['ms_per_token']} vs "
            f"{out['gather']['ms_per_token']} ms/token")
    return out


def _serve_sparse_reads_compare(*, num_slots=2, chunk_steps=8):
    """Dense-reads vs sparsity-aware decode reads over the SAME burst —
    the record ISSUE 12's acceptance names. Builds its own config: the
    shared bench config has no sparse layers, and the tiny 24-token
    sequence fits inside one VariableSparsity window (visibility would
    degenerate to everything-visible), so this uses an ALL-sparse stack
    (>= half sparse layers, trivially) with ``sparse_block=4`` (window
    = 16 tokens) over a 72-token sequence — every decode position
    sees <= 3 of its up-to-9 pages.

    Two leg PAIRS over identical fully-provisioned paged engines and an
    identical request stream — for each impl (the Pallas kernel and the
    dense-view gather), dense reads vs sparsity-aware reads. ALWAYS
    asserted: zero WITHIN-IMPL token mismatches (skipped pages carry
    exactly-zero attention weight, so turning sparse reads on must not
    move a single token), ONE decode compile per leg (the static
    visibility tables must not retrace), and modeled sparse read-bytes
    <= 0.5x dense for both impls (``ops.paged_attention.
    modeled_kv_read_bytes_per_token``; HBM counters are not
    host-observable so bytes are modeled, time is measured).
    Kernel-vs-gather agreement is recorded unasserted
    (``cross_impl_mismatches``) — bench runs bf16 params, where the
    kernel's f32 accumulation is deliberately not bit-matched to the
    gather's bf16 scores (the paged_attn_compare contract; the f32
    byte-identity is pinned in tests/test_sparse_reads.py). The
    ms/token win is asserted on REAL TPU only — on CPU the kernel runs
    under the Pallas interpreter, whose emulation overhead is not the
    hardware's (``asserted``: false)."""
    import numpy as np

    import jax
    import jax.numpy as jnp

    from dalle_pytorch_tpu.models import dalle as D
    from dalle_pytorch_tpu.models import vae as V
    from dalle_pytorch_tpu.ops import paged_attention as PA
    from dalle_pytorch_tpu.serve import Request, RequestQueue, \
        SamplingParams
    from dalle_pytorch_tpu.serve.engine import Engine

    vcfg = V.VAEConfig(image_size=32, num_tokens=32, codebook_dim=32,
                       num_layers=2, hidden_dim=8)
    cfg = D.DALLEConfig(dim=32, depth=2, vae=vcfg, num_text_tokens=64,
                        text_seq_len=8, heads=2, dim_head=16,
                        sparse_attn=True, sparse_block=4)
    params = jax.device_put(D.dalle_init(jax.random.PRNGKey(0), cfg,
                                         dtype=jnp.bfloat16))
    page_size = 8
    prompt_len = min(4, cfg.text_seq_len)
    n_req = 2 * num_slots
    tokens_per_req = cfg.seq_len - prompt_len
    on_tpu = jax.default_backend() == "tpu"
    out = {"page_size": page_size, "chunk_steps": chunk_steps,
           "requests": n_req, "seq_len": cfg.seq_len,
           "sparse_pattern": list(cfg.transformer.sparse_pattern),
           "asserted": on_tpu}
    legs = (("dense_reads", "kernel", False),
            ("sparse_reads", "kernel", True),
            ("dense_reads_gather", "gather", False),
            ("sparse_reads_gather", "gather", True))
    toks = {}
    for name, impl, sparse in legs:
        queue = RequestQueue(max_depth=2 * n_req + 4)
        engine = Engine(params, cfg, queue, num_slots=num_slots,
                        chunk_steps=chunk_steps, kv="paged",
                        page_size=page_size, paged_attn=impl,
                        sparse_reads=sparse)
        # warm the decode program + prefill bucket outside the timing
        h = queue.submit(Request(codes=(1,) * prompt_len, seed=0,
                                 sampling=SamplingParams()))
        engine.run_until_idle()
        h.result(timeout=120)
        t0 = time.perf_counter()
        handles = [queue.submit(Request(
            codes=(1 + i % 7,) * prompt_len, seed=i,
            sampling=SamplingParams())) for i in range(n_req)]
        engine.run_until_idle()
        wall = time.perf_counter() - t0
        results = [h.result(timeout=120) for h in handles]
        ok = sum(r.status == "ok" for r in results)
        if ok != n_req:
            raise AssertionError(
                f"sparse_reads leg {name}: only {ok}/{n_req} completed")
        snap = engine.stats()
        if snap["decode_compiles"] != 1:
            raise AssertionError(
                f"sparse_reads leg {name}: decode compiled "
                f"{snap['decode_compiles']} times — the static "
                f"visibility tables must live inside the ONE fused "
                f"decode program")
        toks[name] = [np.asarray(r.tokens) for r in results]
        out[name] = {
            "paged_attn": impl,
            "sparse_reads": sparse,
            "wall_s": round(wall, 4),
            "ms_per_token": round(
                1e3 * wall / (n_req * tokens_per_req), 4),
            "kv_read_bytes_per_token": int(
                PA.modeled_kv_read_bytes_per_token(
                    depth=cfg.transformer.depth,
                    heads=cfg.transformer.heads,
                    dim_head=cfg.transformer.dim_head,
                    total_len=cfg.seq_len, page_size=page_size,
                    prompt_len=prompt_len, itemsize=2, impl=impl,
                    sparse_reads=sparse,
                    sparse_pattern=(cfg.transformer.sparse_pattern
                                    if sparse else None),
                    sparse_block=cfg.transformer.sparse_block)),
            "decode_compiles": snap["decode_compiles"],
        }
    out["token_mismatches"] = int(sum(
        not np.array_equal(a, b)
        for dense_leg, sparse_leg in (("dense_reads", "sparse_reads"),
                                      ("dense_reads_gather",
                                       "sparse_reads_gather"))
        for a, b in zip(toks[dense_leg], toks[sparse_leg])))
    if out["token_mismatches"]:
        raise AssertionError(
            f"sparsity-aware reads moved tokens: "
            f"{out['token_mismatches']} mismatched streams — skipped "
            f"pages must carry exactly-zero attention weight")
    out["cross_impl_mismatches"] = int(sum(
        not np.array_equal(a, b)
        for a, b in zip(toks["dense_reads"], toks["dense_reads_gather"])))
    for dense_leg, sparse_leg in (("dense_reads", "sparse_reads"),
                                  ("dense_reads_gather",
                                   "sparse_reads_gather")):
        dense_b = out[dense_leg]["kv_read_bytes_per_token"]
        sparse_b = out[sparse_leg]["kv_read_bytes_per_token"]
        if sparse_b > 0.5 * dense_b:
            raise AssertionError(
                f"sparsity-aware reads did not halve the modeled KV "
                f"read traffic ({sparse_leg}): {sparse_b} vs {dense_b} "
                f"bytes/token on an all-sparse config")
    out["read_bytes_ratio"] = round(
        out["dense_reads"]["kv_read_bytes_per_token"]
        / max(out["sparse_reads"]["kv_read_bytes_per_token"], 1), 2)
    if on_tpu and out["sparse_reads"]["ms_per_token"] \
            >= out["dense_reads"]["ms_per_token"]:
        raise AssertionError(
            f"sparsity-aware reads did not beat dense reads on "
            f"hardware: {out['sparse_reads']['ms_per_token']} vs "
            f"{out['dense_reads']['ms_per_token']} ms/token")
    return out


def _serve_spec_compare(params, cfg, *, k, num_slots=2, chunk_steps=4):
    """Eager vs draft-and-verify speculative decode over the SAME burst
    — the record ISSUE 19's acceptance names. Two identical dense
    engines, one with ``speculative=k`` and a shallow draft head (the
    first ``max(depth//4, 1)`` transformer layers), run the same seeded
    requests; the record carries measured ``gen_ms_per_token`` for both
    legs, the achieved ``acceptance_rate`` (delivered / proposed — 1.0
    means every draft matched, 1/k is the total-rejection floor), and
    ``rounds_per_image``.

    ALWAYS asserted, both backends: zero token mismatches between the
    legs — speculation is a latency optimisation, not a sampler; the
    verify pass recomputes exactly what eager would have emitted, so a
    single moved token is a correctness failure — ONE decode compile
    per leg (the k-wide verify is one program, not one per offset), and
    the acceptance rate inside [1/k, 1].

    The >=2x speedup is asserted on REAL TPU only, and only when the
    (k, draft depth) pair can mathematically deliver it: the ideal
    per-round cost is (k-1) draft steps at depth_d/depth of a full step
    plus one k-wide verify ~ one full step, so
    ``ideal_speedup = k / ((k-1)*d/depth + 1)``. Random bench weights
    give a shallow draft no predictive power, so the measured
    acceptance is near the floor and the measured speedup tells you
    about round overhead, not the contract; the asserted number is the
    ACCEPTANCE-WEIGHTED projection — measured ms/token scaled by
    achieved tokens-per-round vs the full-acceptance k
    (``projected_ms_per_token`` = round cost is a constant of the
    compiled program, only delivery varies with acceptance). On CPU the
    record is report-only (``asserted``: false), which is what CI's
    serve-perf speculative leg runs."""
    import numpy as np

    import jax

    from dalle_pytorch_tpu.serve import Request, RequestQueue, \
        SamplingParams
    from dalle_pytorch_tpu.serve.engine import Engine

    depth = cfg.transformer.depth
    draft_layers = max(depth // 4, 1)
    prompt_len = min(4, cfg.text_seq_len)
    n_req = 2 * num_slots
    tokens_per_req = cfg.seq_len - prompt_len
    on_tpu = jax.default_backend() == "tpu"
    ideal_speedup = k / ((k - 1) * draft_layers / depth + 1.0)
    out = {"k": k, "draft_layers": draft_layers, "depth": depth,
           "chunk_steps": chunk_steps, "requests": n_req,
           "ideal_speedup": round(ideal_speedup, 3),
           "asserted": on_tpu and ideal_speedup >= 2.0}
    toks = {}
    for name, spec in (("eager", 0), ("speculative", k)):
        queue = RequestQueue(max_depth=2 * n_req + 4)
        engine = Engine(params, cfg, queue, num_slots=num_slots,
                        chunk_steps=chunk_steps, speculative=spec,
                        draft_layers=draft_layers if spec else 0)
        # warm the decode program + prefill bucket outside the timing
        h = queue.submit(Request(codes=(1,) * prompt_len, seed=0,
                                 sampling=SamplingParams()))
        engine.run_until_idle()
        h.result(timeout=120)
        t0 = time.perf_counter()
        handles = [queue.submit(Request(
            codes=(1 + i % 7,) * prompt_len, seed=i,
            sampling=SamplingParams())) for i in range(n_req)]
        engine.run_until_idle()
        wall = time.perf_counter() - t0
        results = [h.result(timeout=120) for h in handles]
        ok = sum(r.status == "ok" for r in results)
        if ok != n_req:
            raise AssertionError(
                f"spec leg {name}: only {ok}/{n_req} completed")
        snap = engine.stats()
        if snap["decode_compiles"] != 1:
            raise AssertionError(
                f"spec leg {name}: decode compiled "
                f"{snap['decode_compiles']} times — the k-wide verify "
                f"must be ONE program riding the fused chunk, not one "
                f"per offset")
        toks[name] = [np.asarray(r.tokens) for r in results]
        leg = {
            "wall_s": round(wall, 4),
            "gen_ms_per_token": round(
                1e3 * wall / (n_req * tokens_per_req), 4),
            "decode_compiles": snap["decode_compiles"],
        }
        if spec:
            rate = snap["spec_acceptance_rate"]
            if not (1.0 / k - 1e-6 <= rate <= 1.0 + 1e-9):
                raise AssertionError(
                    f"spec acceptance_rate {rate} outside [1/{k}, 1] — "
                    f"the verify equality test is broken")
            leg["acceptance_rate"] = rate
            leg["tokens_per_round"] = snap["spec_tokens_per_round"]
            leg["rounds_per_image"] = round(
                snap["spec_rounds"] / n_req, 2)
        out[name] = leg
    out["token_mismatches"] = int(sum(
        not np.array_equal(a, b)
        for a, b in zip(toks["eager"], toks["speculative"])))
    if out["token_mismatches"]:
        raise AssertionError(
            f"speculative decode moved tokens: "
            f"{out['token_mismatches']} mismatched streams — the "
            f"verify pass must recompute exactly the eager sampler's "
            f"output")
    spec_leg = out["speculative"]
    out["speedup"] = round(out["eager"]["gen_ms_per_token"]
                           / max(spec_leg["gen_ms_per_token"], 1e-9), 3)
    # round cost is a constant of the compiled program; at full
    # acceptance every round delivers k tokens instead of the measured
    # tokens_per_round, so ms/token scales by that ratio
    projected = spec_leg["gen_ms_per_token"] \
        * spec_leg["tokens_per_round"] / k
    out["projected_ms_per_token"] = round(projected, 4)
    out["projected_speedup"] = round(
        out["eager"]["gen_ms_per_token"] / max(projected, 1e-9), 3)
    if out["asserted"] and out["projected_speedup"] < 2.0:
        raise AssertionError(
            f"speculative decode did not reach 2x acceptance-weighted "
            f"gen_ms_per_token on hardware: projected "
            f"{out['projected_speedup']}x (ideal "
            f"{out['ideal_speedup']}x at k={k}, d={draft_layers})")
    return out


def _serve_prefix_compare(*, num_slots=4, chunk_steps=8, n_samples=4):
    """Cold vs WARM admission over the prefix cache, plus the guided-
    pair cost — the record ISSUE 13's acceptance names. One paged
    prefix-cache engine and one prefix-blind reference engine (both
    compiled once), asserted legs:

      * ``fanout``: N samples of one prompt admitted together allocate
        the shared prompt span ONCE — peak physical pages <= pages(1
        request) + N x pages(private span), strictly under the
        refcount-blind engine's measured peak — every stream
        byte-identical to its cold reference;
      * ``warm_prefill``: ZERO prefill dispatches across the warm storm,
        every warm stream byte-identical to its cold run. The host
        milliseconds inside the admission calls are recorded from the
        engine's always-on loop counters (``admit_prefill_s`` over
        ``prefill_runs`` / ``warm_admits``): the time until the call
        RETURNS, not until the device is done — the device's side of
        an admission is the benchmark's ``prefill_device_ms``;
      * ``cfg_pair``: a guided request (cond/uncond pair) against a
        warmed index allocates < 2x the pages of a plain request's
        full map and runs < 2x its ms/token — the prompt and the null
        caption are both shared spans, so only the generated span pays
        double.

    All CPU-safe: pages and dispatch counts are the asserted
    quantities — not kernel ms/token — so this asserts everywhere, not
    just on real TPU."""
    import numpy as np

    import jax
    import jax.numpy as jnp

    from dalle_pytorch_tpu.models import dalle as D
    from dalle_pytorch_tpu.models import vae as V
    from dalle_pytorch_tpu.serve import Request, RequestQueue, pages_for
    from dalle_pytorch_tpu.serve.engine import Engine

    vcfg = V.VAEConfig(image_size=16, num_tokens=32, codebook_dim=16,
                       num_layers=2, hidden_dim=8)
    cfg = D.DALLEConfig(dim=256, depth=4, vae=vcfg, num_text_tokens=64,
                        text_seq_len=32, heads=4, dim_head=64)
    params = jax.device_put(D.dalle_init(jax.random.PRNGKey(0), cfg,
                                         dtype=jnp.bfloat16))
    page_size = 8
    prompt = tuple(1 + (i % 7) for i in range(cfg.text_seq_len))
    t0 = len(prompt)
    full = pages_for(cfg.seq_len, page_size)
    shared_full = t0 // page_size
    slots = max(num_slots, n_samples)
    out = {"page_size": page_size, "chunk_steps": chunk_steps,
           "prompt_len": t0, "seq_len": cfg.seq_len,
           "n_samples": n_samples, "num_slots": slots, "asserted": True}

    def build(prefix_cache):
        queue = RequestQueue(max_depth=4 * slots + 8)
        engine = Engine(params, cfg, queue, num_slots=slots,
                        chunk_steps=chunk_steps, kv="paged",
                        page_size=page_size, prefix_cache=prefix_cache)
        return engine, queue

    def run(engine, queue, reqs):
        handles = [queue.submit(r) for r in reqs]
        t_start = time.perf_counter()
        engine.run_until_idle()
        wall = time.perf_counter() - t_start
        toks = []
        for h in handles:
            res = h.result(timeout=300)
            if res.status != "ok":
                raise AssertionError(
                    f"prefix_compare request failed: {res.status} "
                    f"{res.reason}")
            toks.append(np.asarray(res.tokens))
        return toks, wall

    engine, queue = build(prefix_cache=True)
    ref_engine, ref_queue = build(prefix_cache=False)

    # -- fanout FIRST (clean lifetime peaks on both engines) ------------
    _progress(f"prefix: {n_samples}-sample fan-out of one prompt "
              f"(compiles the four programs)")
    reqs = [Request(codes=prompt, seed=s) for s in range(n_samples)]
    toks, _ = run(engine, queue, reqs)
    want, _ = run(ref_engine, ref_queue, reqs)
    mism = sum(not np.array_equal(a, b) for a, b in zip(toks, want))
    if mism:
        raise AssertionError(
            f"fanout: {mism} of {n_samples} shared-prompt streams "
            f"diverged from their cold runs")
    bound = full + n_samples * (full - shared_full)
    peak, blind = engine.alloc.peak_in_use, ref_engine.alloc.peak_in_use
    if peak > bound:
        raise AssertionError(
            f"fanout peak {peak} pages > bound {bound} (pages(1 "
            f"request) + N x pages(private span)) — the shared span "
            f"must be allocated once")
    if peak >= blind:
        raise AssertionError(
            f"fanout peak {peak} pages >= the refcount-blind engine's "
            f"{blind} — sharing saved nothing")
    out["fanout"] = {"peak_pages": peak, "peak_pages_bound": bound,
                     "peak_pages_blind": blind,
                     "pages_shared": shared_full,
                     "token_mismatches": 0}

    # -- warm_prefill: timed cold storm, then a same-prompt warm storm --
    _progress("prefix: timed cold prefills vs warm admissions")
    cold_reqs = [Request(codes=tuple((1 + i + j) % 7 + 1
                                     for j in range(t0)), seed=i)
                 for i in range(3)]
    s0 = engine.stats()
    for r in cold_reqs:
        run(engine, queue, [r])
    s1 = engine.stats()
    runs_before = engine.prefill_runs
    warm_reqs = [Request(codes=cold_reqs[-1].codes, seed=100 + i)
                 for i in range(4)]
    warm_toks = [run(engine, queue, [r])[0][0] for r in warm_reqs]
    if engine.prefill_runs != runs_before:
        raise AssertionError(
            f"warm storm dispatched {engine.prefill_runs - runs_before} "
            f"prefills — warm admission must run zero")
    for r, got in zip(warm_reqs, warm_toks):
        want_r, _ = run(ref_engine, ref_queue, [r])
        if not np.array_equal(got, want_r[0]):
            raise AssertionError(
                f"warm-hit tokens diverged from the cold run "
                f"(seed {r.seed})")
    stats = engine.stats()

    def dispatch_ms(a, b, key):
        return round(1e3 * (b["admit_prefill_s"] - a["admit_prefill_s"])
                     / max(b[key] - a[key], 1), 4)

    out["warm_prefill"] = {
        "cold_admit_dispatch_ms": dispatch_ms(s0, s1, "prefill_runs"),
        "warm_admit_dispatch_ms": dispatch_ms(s1, stats, "warm_admits"),
        "warm_prefill_runs": stats["prefill_runs"] - s1["prefill_runs"],
        "prefix_hits": stats["prefix_hits"],
        "prefill_runs": stats["prefill_runs"],
        "token_mismatches": 0,
    }

    # -- cfg_pair: guided vs plain on the warmed index ------------------
    _progress("prefix: guided-pair page/latency cost vs plain")
    run(engine, queue, [Request(codes=(0,) * t0, seed=1)])  # null entry
    run(engine, queue, [Request(codes=prompt, seed=7, cfg_scale=2.0)])
    allocs0 = engine.alloc.allocs
    _, plain_wall = run(engine, queue, [Request(codes=prompt, seed=8)])
    plain_fresh = engine.alloc.allocs - allocs0
    allocs1 = engine.alloc.allocs
    _, cfg_wall = run(engine, queue,
                      [Request(codes=prompt, seed=9, cfg_scale=2.0)])
    cfg_fresh = engine.alloc.allocs - allocs1
    tokens_per_req = cfg.seq_len - t0
    plain_ms = 1e3 * plain_wall / tokens_per_req
    cfg_ms = 1e3 * cfg_wall / tokens_per_req
    # pages: what the pair newly ALLOCATES (shared spans cost zero
    # fresh pages) vs a plain request's full map — strictly under 2x
    if cfg_fresh >= 2 * full:
        raise AssertionError(
            f"guided pair allocated {cfg_fresh} fresh pages >= 2x a "
            f"plain request's {full} — the prompt/null spans must "
            f"share physically")
    if cfg_ms >= 2 * plain_ms:
        raise AssertionError(
            f"guided ms/token {cfg_ms:.3f} >= 2x plain "
            f"{plain_ms:.3f} — the pair rides the same fused chunks")
    out["cfg_pair"] = {
        "plain_ms_per_token": round(plain_ms, 4),
        "cfg_ms_per_token": round(cfg_ms, 4),
        "ms_ratio": round(cfg_ms / max(plain_ms, 1e-9), 3),
        "plain_pages_full": full,
        "plain_fresh_pages": int(plain_fresh),
        "cfg_fresh_pages": int(cfg_fresh),
        "pages_ratio": round(cfg_fresh / full, 3),
        "cfg_pairs": engine.cfg_pairs,
    }
    return out


def _serve_fanout_compare(*, n_samples=4, chunk_steps=8):
    """The streaming/fan-out tier record (docs/SERVING.md 'Streaming,
    fan-out & variable resolution') — one InferenceServer (paged KV +
    prefix cache + previews + CLIP rerank), four asserted legs:

      * ``best_of_n``: ONE ``submit(n_samples=N, stream=True)`` call
        returns a ranked group. Every sample completes OK; the group's
        lifetime page peak is <= ONE prompt span + N generation spans
        (the COW bound — strictly under N independent full maps), and
        the engine's ``pages_shared`` proves the prompt prefill was
        paid once; the ranked ``samples`` list is CLIP-score
        descending.
      * ``stream_identity``: the multiplexed SSE channel's per-sample
        token events, reassembled by absolute position, are
        byte-identical to each member's terminal result — and each
        member's tokens are byte-identical to a STANDALONE non-streamed
        request submitted with the derived ``sample_seed(seed, i)``
        (streaming moves observation, never computation).
      * ``preview_final``: each sample's ``final=True`` preview frame
        unpacks bit-equal to its result image (same zero-padded row
        through the same jitted VAE program, by construction).
      * ``short_grid``: ``image_seq_len_override = L/2`` completes with
        exactly L/2 tokens that are the PREFIX of the full-resolution
        run at the same seed (the autoregressive stream is causal, so
        a shorter grid is a truncation, not a different sample) —
        train-free variable resolution riding the same programs.

    All CPU-safe (pages / counts / byte-equality, no kernel timing);
    raises AssertionError on violation — CI's serve-stream smoke greps
    the structured ``"error"`` field like every sibling compare leg."""
    import numpy as np

    import jax

    from dalle_pytorch_tpu.models import clip as C
    from dalle_pytorch_tpu.models import dalle as D
    from dalle_pytorch_tpu.models import vae as V
    from dalle_pytorch_tpu.serve import pages_for, sample_seed, \
        unpack_image
    from dalle_pytorch_tpu.serve.server import InferenceServer

    # tied codebook: vae.codebook_dim must equal the dalle dim
    vcfg = V.VAEConfig(image_size=16, num_tokens=32, codebook_dim=32,
                       num_layers=2, hidden_dim=8)
    cfg = D.DALLEConfig(dim=32, depth=2, vae=vcfg, num_text_tokens=64,
                        text_seq_len=16, heads=2, dim_head=16)
    ccfg = C.CLIPConfig(dim_text=32, dim_image=32, dim_latent=24,
                        num_text_tokens=cfg.num_text_tokens,
                        text_enc_depth=2,
                        text_seq_len=cfg.text_seq_len, text_heads=2,
                        visual_enc_depth=2, visual_heads=2,
                        visual_image_size=vcfg.image_size,
                        visual_patch_size=8, sparse_attn=False)
    key = jax.random.PRNGKey(0)
    vae_params = V.vae_init(jax.random.fold_in(key, 1), vcfg)
    params = jax.device_put(D.dalle_init(key, cfg, vae_params))
    clip_params = jax.device_put(C.clip_init(
        jax.random.fold_in(key, 2), ccfg))

    n = int(n_samples)
    page_size = 8
    prompt = tuple(1 + (i % 7) for i in range(cfg.text_seq_len))
    t0 = len(prompt)
    full = pages_for(cfg.seq_len, page_size)
    shared = t0 // page_size
    # the COW bound the acceptance names: the prompt span allocated
    # ONCE plus N private generation spans
    bound = shared + n * (full - shared)
    out = {"n_samples": n, "page_size": page_size, "prompt_len": t0,
           "seq_len": cfg.seq_len, "chunk_steps": chunk_steps,
           "asserted": True}

    server = InferenceServer(
        params, vae_params, cfg, num_slots=max(n, 2),
        queue_depth=4 * n + 8, chunk_steps=chunk_steps, kv="paged",
        page_size=page_size, prefix_cache=True, preview_every=2,
        clip_params=clip_params, clip_cfg=ccfg,
        weights_version="v0").start()
    try:
        # -- best_of_n FIRST: a clean lifetime page peak -----------------
        _progress(f"fanout: best-of-{n} group (compiles prefill + "
                  f"fused decode + VAE + CLIP)")
        group = server.submit(prompt, seed=7, n_samples=n, stream=True)
        streamed: dict = {i: {} for i in range(n)}   # pos -> tokens
        finals: dict = {}
        events = 0
        for ev in group.sink.events():
            events += 1
            if ev["event"] == "tokens":
                streamed[ev["sample"]][ev["pos"]] = ev["tokens"]
            elif ev["event"] == "preview" and ev.get("final"):
                finals[ev["sample"]] = unpack_image(ev["image"])
        res = group.result(timeout=300)
        if not res.ok:
            raise AssertionError(
                f"best-of-{n} group failed: {res.status} ({res.reason})")
        if len(res.samples) != n \
                or any(not s.ok for s in res.samples):
            raise AssertionError(
                f"group must complete ALL {n} samples: "
                f"{[s.status for s in res.samples]}")
        scores = [s.clip_score for s in res.samples]
        if any(sc is None for sc in scores) \
                or any(a < b for a, b in zip(scores, scores[1:])):
            raise AssertionError(
                f"samples must be CLIP-score ranked descending, got "
                f"{scores}")
        peak = server.engine.alloc.peak_in_use
        snap = server.engine.stats()
        if peak > bound:
            raise AssertionError(
                f"fanout peak {peak} pages > COW bound {bound} (1 "
                f"prompt span + {n} generation spans) — the shared "
                f"prompt must be allocated once")
        # pages_shared is a live gauge (drops back once the group's refs
        # release); the cumulative proof the prompt was paid once is the
        # warm-hit count + the retains the siblings took on the leader's
        # span (each warm sibling retains `shared` pages instead of
        # allocating them)
        if snap["prefix_hits"] < n - 1 \
                or server.engine.alloc.retains < (n - 1) * shared:
            raise AssertionError(
                f"prompt span not shared: prefix_hits="
                f"{snap['prefix_hits']} (want >= {n - 1}), retains="
                f"{server.engine.alloc.retains} (want >= "
                f"{(n - 1) * shared})")
        out["best_of_n"] = {
            "completed": n, "events": events,
            "peak_pages": int(peak), "peak_pages_bound": int(bound),
            "prefix_hits": int(snap["prefix_hits"]),
            "pages_retained": int(server.engine.alloc.retains),
            "best_clip_score": round(float(scores[0]), 6),
        }

        # -- stream_identity: SSE bytes == results == standalones --------
        _progress("fanout: streamed-vs-standalone byte identity")
        members = [m.result(timeout=5) for m in group.members]
        mismatches = 0
        for i, m in enumerate(members):
            toks = []
            for pos in sorted(streamed[i]):
                toks.extend(streamed[i][pos])
            want = np.asarray(m.tokens)
            got = np.asarray(toks[-len(m.tokens):], want.dtype)
            if not np.array_equal(got, want):
                mismatches += 1
            alone = server.generate(prompt, seed=sample_seed(7, i),
                                    timeout=300)
            if not alone.ok or not np.array_equal(
                    np.asarray(alone.tokens), want):
                mismatches += 1
        if mismatches:
            raise AssertionError(
                f"stream identity broke: {mismatches} of {n} samples "
                f"diverged between the SSE event stream, the member "
                f"result, and the standalone sample_seed run")
        if any(i not in finals for i in range(n)) or any(
                not np.array_equal(finals[i], members[i].image)
                for i in range(n)):
            raise AssertionError(
                "final preview frame != non-streamed result image — "
                "the closing SSE frame must be the result, bit-exact")
        out["stream_identity"] = {"token_mismatches": 0,
                                  "final_frames": len(finals)}

        # -- short_grid: override is a prefix of the full-res run --------
        _progress("fanout: image_seq_len_override prefix identity")
        L = cfg.image_seq_len // 2
        short = server.generate(prompt, seed=7,
                                image_seq_len_override=L, timeout=300)
        if not short.ok or len(short.tokens) != L:
            raise AssertionError(
                f"override run: {short.status}, "
                f"{len(short.tokens or ())} tokens (want {L})")
        full_run = server.generate(prompt, seed=7, timeout=300)
        if not np.array_equal(np.asarray(short.tokens),
                              np.asarray(full_run.tokens)[:L]):
            raise AssertionError(
                "override tokens are not the full-resolution prefix — "
                "the short grid must truncate the same causal stream")
        if short.image is None or short.image.shape \
                != full_run.image.shape:
            raise AssertionError(
                "override result must still decode a full-shape image "
                "from the zero-padded prefix row")
        out["short_grid"] = {"override": L,
                             "tokens": len(short.tokens)}

        # -- the stats surface the CI smoke greps ------------------------
        st = server.stats()
        if st["groups_completed"] < 1 \
                or st["fanout_pages_saved"] < (n - 1) * shared \
                or st["preview_frames"] < n:
            raise AssertionError(
                f"stats must bank the group: groups_completed="
                f"{st['groups_completed']} fanout_pages_saved="
                f"{st['fanout_pages_saved']} preview_frames="
                f"{st['preview_frames']}")
        out["stats"] = {
            "groups_completed": st["groups_completed"],
            "fanout_pages_saved": st["fanout_pages_saved"],
            "preview_frames": st["preview_frames"],
            "streams_active": st["streams_active"],
        }
    finally:
        server.close()
    return out


def _serve_replica_compare(params, cfg, *, replicas, num_slots, n_req,
                           kv, page_size, chunk_steps=8):
    """The replica-set headline: N supervised engines behind one queue
    must beat one engine at the SAME offered load (more slots in flight;
    with one jax device per replica the fused chunks genuinely overlap),
    with the steady state still transfer-clean and the decode program
    compiled exactly once PER REPLICA — and a replica killed mid-sweep
    by the deterministic serve fault must cost zero requests (failover
    reclaims its in-flight work and replays it on the survivors;
    deterministic sampling makes the replay token-exact, which
    tests/test_replica.py pins byte-for-byte). Both halves are ASSERTED,
    not just measured, so CI's serve-faults smoke greps one 'error'
    field."""
    from dalle_pytorch_tpu.analysis import guards
    from dalle_pytorch_tpu.resilience import faults
    from dalle_pytorch_tpu.serve import Request, RequestQueue, \
        SamplingParams
    from dalle_pytorch_tpu.serve.replica import ReplicaSet

    prompt_len = min(4, cfg.text_seq_len)
    # enough offered work to keep every leg queue-bound for several
    # waves (the comparison needs slots, not arrivals, binding)
    n_load = max(n_req, 4 * replicas * num_slots)
    out = {"replicas": replicas, "requests": n_load}

    def build(R, warm=True):
        queue = RequestQueue(max_depth=max(4 * n_load, 16))
        rs = ReplicaSet(params, cfg, queue, replicas=R,
                        num_slots=num_slots, chunk_steps=chunk_steps,
                        kv=kv,
                        page_size=page_size if kv == "paged" else 0)
        if warm:
            # warm every replica's prefill bucket + fused decode
            # program outside the timed/guarded regions (time_steps'
            # warmup discipline)
            handles = [queue.submit(Request(
                codes=(1,) * prompt_len, seed=i,
                sampling=SamplingParams()))
                for i in range(R * num_slots)]
            rs.run_until_idle()
            for h in handles:
                h.result(timeout=120)
        return rs, queue

    def submit_burst(queue):
        return [queue.submit(Request(
            codes=(1 + i % 7,) * prompt_len, seed=i,
            sampling=SamplingParams())) for i in range(n_load)]

    # throughput legs run THREADED (thread per replica + supervisor —
    # the serve_dalle --replicas deployment mode): one replica's host
    # bookkeeping overlaps the others' chunk compute, and with one jax
    # device per replica the chunks themselves overlap. Best-of-2 to
    # shave scheduler noise off a short measurement.
    for R in (1, replicas):
        rs, queue = build(R)
        rs.start()
        best = None
        for _ in range(2):
            t0 = time.perf_counter()
            handles = submit_burst(queue)
            ok = sum(h.result(timeout=120).status == "ok"
                     for h in handles)
            wall = time.perf_counter() - t0
            if ok != n_load:
                raise AssertionError(
                    f"replicas={R}: only {ok}/{n_load} completed")
            best = wall if best is None else min(best, wall)
        rs.close()
        compiles = rs.decode_compiles_per_replica()
        out[f"r{R}"] = {
            "wall_s": round(best, 4),
            "throughput_imgs_per_s": round(n_load / best, 3),
            "decode_compiles_per_replica": compiles,
        }
        if any(c != 1 for c in compiles):
            raise AssertionError(
                f"replicas={R}: decode compiled {compiles} times across "
                f"replicas — the one-compile-per-replica contract broke")
    if out[f"r{replicas}"]["throughput_imgs_per_s"] \
            <= out["r1"]["throughput_imgs_per_s"]:
        raise AssertionError(
            f"{replicas} replicas did not beat 1 at the same offered "
            f"load: {out[f'r{replicas}']['throughput_imgs_per_s']} vs "
            f"{out['r1']['throughput_imgs_per_s']} imgs/s")

    # contract leg, single-threaded drive: the replicated steady state
    # is still TRANSFER-CLEAN (the same guards.no_transfers the K-sweep
    # runs under; routing hand-offs are host-side, harvests stay one
    # explicit device_get per chunk per replica)
    rs, queue = build(replicas)
    with guards.no_transfers():
        point = _serve_load_point(rs, queue, 1000.0,
                                  min(n_req, n_load), prompt_len)
    if point["completed"] != min(n_req, n_load):
        raise AssertionError(
            f"transfer-clean leg: only {point['completed']} completed")
    out["transfer_clean"] = True

    # the failover half: kill the last replica mid-sweep (after its
    # 2nd fused chunk) and require every request to complete anyway.
    # UNWARMED on purpose: the crash fault compares against the
    # engine's lifetime chunk counter, and a warmed victim would die
    # on its first post-injection step — before the burst is
    # mid-decode — making the zero-loss assertion trivially true
    rs, queue = build(replicas, warm=False)
    with faults.injected(fault_replica=replicas - 1,
                         replica_crash_at_chunk=2):
        handles = submit_burst(queue)
        rs.run_until_idle()
    ok = sum(h.result(timeout=60).status == "ok" for h in handles)
    out["failover"] = {"requests": n_load, "completed": ok,
                       "failovers": rs.failovers,
                       "reclaimed": rs.reclaimed}
    if rs.failovers < 1:
        raise AssertionError("injected replica kill never fired — the "
                             "failover leg proved nothing")
    if ok != n_load:
        raise AssertionError(
            f"replica kill lost requests: {ok}/{n_load} completed")
    return out


def _serve_isolation_compare(params, cfg, *, replicas, num_slots, n_req,
                             kv, page_size, chunk_steps=8):
    """The isolation tax, TRACKED rather than guessed: the same replica
    set under the same offered burst, thread-isolated (shared process)
    vs process-isolated (child-process engines behind serve/ipc.py),
    recording ms/token for both legs plus the process leg's measured
    IPC lag (child snapshot stamp -> parent absorb; perf_counter is
    CLOCK_MONOTONIC on Linux, one epoch across processes). Then the
    robustness half the isolation exists for, ASSERTED: a real SIGKILL
    of a child replica mid-sweep (the deterministic hard fault) loses
    zero requests — its shadow-reclaimed work replays on the survivor
    and the exit signal is decoded on the supervisor's record."""
    import statistics as stats_mod

    from dalle_pytorch_tpu.resilience import faults
    from dalle_pytorch_tpu.serve import Request, RequestQueue, \
        SamplingParams
    from dalle_pytorch_tpu.serve.replica import (ReplicaSet,
                                                 check_chip_ownership)

    # this process has run thread-mode engines on the backend already:
    # on a TPU it holds the chips, and the process leg's children could
    # only die on libtpu's lock — refuse typed, before any leg runs
    check_chip_ownership("process", None)
    prompt_len = min(4, cfg.text_seq_len)
    n_load = max(n_req, 4 * replicas * num_slots)
    tokens_per_req = cfg.seq_len - prompt_len
    out = {"replicas": replicas, "requests": n_load,
           "tokens_per_request": tokens_per_req}

    def build(iso):
        queue = RequestQueue(max_depth=max(4 * n_load, 16))
        rs = ReplicaSet(params, cfg, queue, replicas=replicas,
                        num_slots=num_slots, chunk_steps=chunk_steps,
                        kv=kv,
                        page_size=page_size if kv == "paged" else 0,
                        isolation=iso)
        return rs, queue

    def submit_burst(queue):
        return [queue.submit(Request(
            codes=(1 + i % 7,) * prompt_len, seed=i,
            sampling=SamplingParams())) for i in range(n_load)]

    for iso in ("thread", "process"):
        rs, queue = build(iso)
        rs.start()
        # warm every replica's programs outside the timed window (the
        # process leg's children also populate their jit caches here)
        warm = [queue.submit(Request(codes=(1,) * prompt_len, seed=i,
                                     sampling=SamplingParams()))
                for i in range(replicas * num_slots)]
        for h in warm:
            h.result(timeout=300)
        best = None
        for _ in range(2):          # best-of-2: shave scheduler noise
            t0 = time.perf_counter()
            handles = submit_burst(queue)
            ok = sum(h.result(timeout=300).status == "ok"
                     for h in handles)
            wall = time.perf_counter() - t0
            if ok != n_load:
                raise AssertionError(
                    f"isolation={iso}: only {ok}/{n_load} completed")
            best = wall if best is None else min(best, wall)
        leg = {
            "wall_s": round(best, 4),
            "throughput_imgs_per_s": round(n_load / best, 3),
            "ms_per_token": round(
                1e3 * best / (n_load * tokens_per_req), 4),
            "decode_compiles_per_replica":
                rs.decode_compiles_per_replica(),
        }
        if iso == "process":
            lags = []
            for r in rs.replicas:
                if r.engine is not None:
                    lags.extend(r.engine.ipc_lag_s)
            if lags:
                lags.sort()
                leg["ipc_lag_ms_mean"] = round(
                    1e3 * stats_mod.fmean(lags), 3)
                leg["ipc_lag_ms_p95"] = round(
                    1e3 * lags[min(int(0.95 * len(lags)),
                                   len(lags) - 1)], 3)
        rs.close()
        if any(c != 1 for c in leg["decode_compiles_per_replica"]):
            raise AssertionError(
                f"isolation={iso}: decode compiled "
                f"{leg['decode_compiles_per_replica']} times — the "
                f"one-compile-per-replica contract broke")
        out[iso] = leg
    thr = out["thread"]["ms_per_token"]
    out["isolation_tax_pct"] = round(
        100.0 * (out["process"]["ms_per_token"] - thr) / thr, 1)

    # the hard-kill half: a REAL `kill -9` of the last replica's child
    # after its 2nd fused chunk (unwarmed on purpose — the fault keys
    # on the child's lifetime chunk counter, and a warmed victim would
    # die before the burst is mid-decode). Zero lost requests, the
    # exit signal decoded, the killed replica restarted.
    with faults.injected(fault_replica=replicas - 1,
                         replica_sigkill_at_chunk=2):
        # constructed INSIDE the plan: hard-fault plans cross the
        # process boundary at spawn, once per activation
        rs, queue = build("process")
        handles = submit_burst(queue)
        rs.run_until_idle(max_steps=2_000_000)
    ok = sum(h.result(timeout=120).status == "ok" for h in handles)
    victim = rs.replicas[replicas - 1]
    out["failover"] = {"requests": n_load, "completed": ok,
                       "failovers": rs.failovers,
                       "reclaimed": rs.reclaimed,
                       "exit": victim.last_exit,
                       "victim_bringups": victim.bringups}
    rs.close()
    if rs.failovers < 1:
        raise AssertionError("injected child SIGKILL never fired — the "
                             "process failover leg proved nothing")
    if "SIGKILL" not in victim.last_exit:
        raise AssertionError(
            f"child exit decoded as {victim.last_exit!r}, not SIGKILL")
    if ok != n_load:
        raise AssertionError(
            f"child SIGKILL lost requests: {ok}/{n_load} completed")
    return out


def _serve_transport_compare(params, cfg, *, replicas, num_slots, n_req,
                             kv, page_size, chunk_steps=8):
    """The socket-transport tax, TRACKED rather than guessed: the same
    process-isolated replica set under the same offered burst, frames
    over a duplex pipe vs dial-back TCP (serve/transport.py), recording
    ms/token and the measured IPC lag for both legs. Then the
    robustness half host isolation exists for, ASSERTED: a connection
    reset that tears a frame mid-stream (the deterministic network
    fault) fences the replica on a TYPED protocol error and loses zero
    requests — its shadow-reclaimed work replays on the survivor."""
    import statistics as stats_mod

    from dalle_pytorch_tpu.resilience import faults
    from dalle_pytorch_tpu.serve import Request, RequestQueue, \
        SamplingParams
    from dalle_pytorch_tpu.serve.replica import (ReplicaSet,
                                                 check_chip_ownership)

    check_chip_ownership("process", None)   # see _serve_isolation_compare
    prompt_len = min(4, cfg.text_seq_len)
    n_load = max(n_req, 4 * replicas * num_slots)
    tokens_per_req = cfg.seq_len - prompt_len
    out = {"replicas": replicas, "requests": n_load,
           "tokens_per_request": tokens_per_req}

    def build(transport):
        queue = RequestQueue(max_depth=max(4 * n_load, 16))
        rs = ReplicaSet(params, cfg, queue, replicas=replicas,
                        num_slots=num_slots, chunk_steps=chunk_steps,
                        kv=kv,
                        page_size=page_size if kv == "paged" else 0,
                        isolation="process", transport=transport)
        return rs, queue

    def submit_burst(queue):
        return [queue.submit(Request(
            codes=(1 + i % 7,) * prompt_len, seed=i,
            sampling=SamplingParams())) for i in range(n_load)]

    for transport in ("pipe", "socket"):
        rs, queue = build(transport)
        # close on EVERY exit: a failed assertion must not leak live
        # child workers + the listener into the rest of the bench run
        try:
            rs.start()
            warm = [queue.submit(Request(codes=(1,) * prompt_len,
                                         seed=i,
                                         sampling=SamplingParams()))
                    for i in range(replicas * num_slots)]
            for h in warm:
                h.result(timeout=300)
            best = None
            for _ in range(2):      # best-of-2: shave scheduler noise
                t0 = time.perf_counter()
                handles = submit_burst(queue)
                ok = sum(h.result(timeout=300).status == "ok"
                         for h in handles)
                wall = time.perf_counter() - t0
                if ok != n_load:
                    raise AssertionError(
                        f"transport={transport}: only {ok}/{n_load} "
                        f"completed")
                best = wall if best is None else min(best, wall)
            lags = []
            for r in rs.replicas:
                if r.engine is not None:
                    lags.extend(r.engine.ipc_lag_s)
            leg = {
                "wall_s": round(best, 4),
                "throughput_imgs_per_s": round(n_load / best, 3),
                "ms_per_token": round(
                    1e3 * best / (n_load * tokens_per_req), 4),
                "decode_compiles_per_replica":
                    rs.decode_compiles_per_replica(),
            }
            if lags:
                lags.sort()
                leg["ipc_lag_ms_mean"] = round(
                    1e3 * stats_mod.fmean(lags), 3)
                leg["ipc_lag_ms_p95"] = round(
                    1e3 * lags[min(int(0.95 * len(lags)),
                                   len(lags) - 1)], 3)
        finally:
            rs.close()
        if any(c != 1 for c in leg["decode_compiles_per_replica"]):
            raise AssertionError(
                f"transport={transport}: decode compiled "
                f"{leg['decode_compiles_per_replica']} times — the "
                f"one-compile-per-replica contract broke")
        out[transport] = leg
    pipe_ms = out["pipe"]["ms_per_token"]
    out["socket_tax_pct"] = round(
        100.0 * (out["socket"]["ms_per_token"] - pipe_ms) / pipe_ms, 1)

    # the network-fault half: a connection reset that tears a heartbeat
    # frame mid-stream on the last replica after its 2nd fused chunk.
    # Zero lost requests, the fence reason typed (protocol error), the
    # victim restarted.
    events = []

    class _Sink:
        def event(self, **rec):
            events.append(rec)

    with faults.injected(fault_replica=replicas - 1,
                         replica_conn_reset_at_chunk=2):
        queue = RequestQueue(max_depth=max(4 * n_load, 16))
        rs = ReplicaSet(params, cfg, queue, replicas=replicas,
                        num_slots=num_slots, chunk_steps=chunk_steps,
                        kv=kv,
                        page_size=page_size if kv == "paged" else 0,
                        isolation="process", transport="socket",
                        metrics=_Sink())
        ok = 0
        try:
            handles = submit_burst(queue)
            rs.run_until_idle(max_steps=2_000_000)
            ok = sum(h.result(timeout=120).status == "ok"
                     for h in handles)
        finally:
            fenced = [e for e in events
                      if e.get("kind") == "serve_replica_fenced"]
            out["conn_reset_failover"] = {
                "requests": n_load, "completed": ok,
                "failovers": rs.failovers, "reclaimed": rs.reclaimed,
                "fence_reason": fenced[0]["reason"] if fenced else ""}
            rs.close()
    if rs.failovers < 1:
        raise AssertionError("injected connection reset never fired — "
                             "the transport failover leg proved "
                             "nothing")
    if not fenced or "protocol error" not in fenced[0]["reason"]:
        raise AssertionError(
            f"conn reset fenced untyped: {fenced!r}")
    if ok != n_load:
        raise AssertionError(
            f"connection reset lost requests: {ok}/{n_load} completed")
    return out


def _serve_elastic_compare(params, cfg, *, num_slots, chunk_steps=8):
    """The elastic-fleet headline (docs/SERVING.md 'Elastic fleet'): an
    offered-load ramp through a fleet that RESHAPES mid-sweep — the
    autoscaler adds a third replica under a burst (off the same /stats
    signals it watches in production: occupancy + queue depth, with
    hysteresis and cooldown), a post-scale wave shows p95 RECOVERING
    (the added capacity drains the same offered load faster than the
    congested 2-replica burst did), and a rolling weight upgrade cycles
    every replica to a second weights generation with traffic in
    flight. Every contract is ASSERTED, not just measured, so CI's
    serve-elastic smoke greps one "error" field: zero requests lost
    through every reshape, at least one structured scale-out decision
    (and one scale-in on the ramp-down), the upgrade covering all three
    replicas, per-phase weights_version counts in the record, and the
    post-upgrade wave stamped entirely with the new generation."""
    import jax
    import jax.numpy as jnp

    from dalle_pytorch_tpu.models import dalle as D
    from dalle_pytorch_tpu.serve import Request, RequestQueue, \
        SamplingParams
    from dalle_pytorch_tpu.serve.autoscale import (AutoscalePolicy,
                                                   Autoscaler)
    from dalle_pytorch_tpu.serve.replica import ReplicaSet

    prompt_len = min(4, cfg.text_seq_len)
    queue = RequestQueue(max_depth=1024)
    rs = ReplicaSet(params, cfg, queue, replicas=2, num_slots=num_slots,
                    chunk_steps=chunk_steps, weights_version="v1",
                    max_replicas=3)
    # aggressive thresholds so the tiny CPU burst breaches quickly;
    # production cadence is the CLI's --autoscale_* knobs
    scaler = Autoscaler(rs, AutoscalePolicy(
        min_replicas=2, max_replicas=3, high_occupancy=0.75,
        low_occupancy=0.05, queue_high=1, breach_ticks=2,
        cooldown_s=0.25))
    params_v2 = jax.device_put(D.dalle_init(jax.random.PRNGKey(1), cfg,
                                            dtype=jnp.bfloat16))
    try:

        phases = {}

        def wave(tag, n, tick):
            t0 = time.perf_counter()
            handles = [queue.submit(Request(
                codes=(1 + i % 7,) * prompt_len, seed=i,
                sampling=SamplingParams())) for i in range(n)]
            while not all(h.done() for h in handles):
                rs.step_once()
                if tick:
                    scaler.tick()
            rs.run_until_idle()
            res = [h.result(timeout=0) for h in handles]
            ok = sum(r.ok for r in res)
            if ok != n:
                raise AssertionError(
                    f"elastic phase {tag!r} lost requests: {ok}/{n} "
                    f"completed ({[r.reason for r in res if not r.ok]})")
            versions = {}
            for r in res:
                versions[r.weights_version] = \
                    versions.get(r.weights_version, 0) + 1
            lats = sorted(r.total_s for r in res)
            rec = {"requests": n, "completed": ok,
                   "wall_s": round(time.perf_counter() - t0, 3),
                   "p95_latency_ms": round(
                       1e3 * lats[min(int(0.95 * n), n - 1)], 1),
                   "weights_versions": versions,
                   "replicas": rs.n_replicas}
            phases[tag] = rec
            return rec

        # warm both replicas' programs outside the measured ramp
        wave("warmup", 2 * num_slots, tick=False)
        # baseline undershoots the occupancy watermark (half the fleet's
        # slots): the scaler must hold a fleet that is merely busy
        base = wave("baseline", num_slots, tick=True)
        if rs.n_replicas != 2:
            raise AssertionError(
                f"autoscaler reshaped under baseline load "
                f"({rs.n_replicas} replicas) — thresholds prove nothing")
        burst = wave("burst", 8 * num_slots, tick=True)
        outs = [d for d in scaler.decisions if d["action"] == "scale_out"]
        if not outs or rs.n_replicas != 3:
            raise AssertionError(
                f"the burst never forced a scale-out (decisions "
                f"{[d['action'] for d in scaler.decisions]}, "
                f"{rs.n_replicas} replicas)")
        post = wave("post_scale", 8 * num_slots, tick=False)
        if post["p95_latency_ms"] > burst["p95_latency_ms"]:
            raise AssertionError(
                f"p95 did not recover after scale-out: "
                f"{post['p95_latency_ms']}ms at 3 replicas vs "
                f"{burst['p95_latency_ms']}ms during the 2->3 burst")

        # rolling upgrade with traffic in flight: submit a wave, cycle the
        # whole (now 3-replica) fleet to v2 while it drains — zero loss,
        # every result stamped with the generation that decoded it
        inflight = [queue.submit(Request(
            codes=(1 + i % 7,) * prompt_len, seed=100 + i,
            sampling=SamplingParams())) for i in range(4 * num_slots)]
        upgrade = rs.rolling_upgrade(version="v2", params=params_v2,
                                     canary_codes=[(1,) * prompt_len],
                                     canaries=2, replica_timeout_s=300)
        rs.run_until_idle()
        res = [h.result(timeout=60) for h in inflight]
        ok = sum(r.ok for r in res)
        if ok != len(inflight):
            raise AssertionError(
                f"rolling upgrade lost requests: {ok}/{len(inflight)}")
        mid_versions = {}
        for r in res:
            mid_versions[r.weights_version] = \
                mid_versions.get(r.weights_version, 0) + 1
        phases["during_upgrade"] = {
            "requests": len(inflight), "completed": ok,
            "weights_versions": mid_versions, "replicas": rs.n_replicas}
        if len(upgrade["replicas"]) != 3:
            raise AssertionError(
                f"upgrade cycled {len(upgrade['replicas'])}/3 replicas")

        final = wave("post_upgrade", 2 * num_slots, tick=False)
        if final["weights_versions"] != {"v2": final["requests"]}:
            raise AssertionError(
                f"post-upgrade wave not fully on v2: "
                f"{final['weights_versions']}")

        # ramp-down: idle ticks must retire the burst replica (hysteresis
        # + cooldown bounded — a few seconds of quiet, not minutes)
        deadline = time.perf_counter() + 30
        while rs.n_replicas > 2 and time.perf_counter() < deadline:
            rs.step_once()
            scaler.tick()
            time.sleep(0.01)
        ins = [d for d in scaler.decisions if d["action"] == "scale_in"]
        if not ins or rs.n_replicas != 2:
            raise AssertionError(
                f"idle ramp-down never scaled in (decisions "
                f"{[d['action'] for d in scaler.decisions]}, "
                f"{rs.n_replicas} replicas)")

        return {
            "phases": phases,
            "scale_events": scaler.decisions,
            "upgrade": upgrade,
            "weights_version_final": rs.weights_version,
            "replicas_final": rs.n_replicas,
            "p95_recovered": post["p95_latency_ms"]
            <= burst["p95_latency_ms"],
            "baseline_p95_ms": base["p95_latency_ms"],
        }
    finally:
        # every sibling compare leg tears its set down; a leaked
        # replica fleet would pin 2-3 KV pools in HBM under the
        # rest of the bench even when this leg errors out
        rs.close()


def _serve_migrate_compare(params, cfg, *, num_slots, page_size,
                           chunk_steps=8):
    """The live-migration headline (docs/SERVING.md 'Live migration &
    disaggregated roles'): two identical 2-replica paged runs that both
    retire replica 0 with its requests MID-STREAM. The migrated leg
    (``remove_replica(drain=True)``) ships each in-flight request's KV
    pages + decode cursor to the survivor, which finishes it without
    re-decoding a token; the replay leg (``drain=False``) takes the
    pre-migration path — fence, reclaim, re-decode from token zero.
    Both legs must complete every request ("zero loss" is table
    stakes either way — replay already guaranteed it); the tokens of
    the two legs must be byte-identical (migration changes WHERE the
    remaining tokens decode, never WHAT they are); and the migrated
    leg's ``migrated_tokens_saved`` must cover at least half the
    tokens the replay leg re-decoded — the whole point of the
    feature, asserted so CI's serve-migrate smoke greps one "error"
    field."""
    from dalle_pytorch_tpu.serve import Request, RequestQueue, \
        SamplingParams
    from dalle_pytorch_tpu.serve.replica import ReplicaSet

    prompt_len = min(4, cfg.text_seq_len)
    n_req = 2 * max(2, num_slots // 2)
    # a full harvest chunk per victim request before the removal: the
    # migration must move requests that are deep enough into decode
    # that replaying them from zero is visibly wasteful
    min_prog = max(2, chunk_steps)

    def leg(drain, tag):
        queue = RequestQueue(max_depth=256)
        rs = ReplicaSet(params, cfg, queue, replicas=2,
                        num_slots=num_slots, chunk_steps=chunk_steps,
                        kv="paged", page_size=page_size,
                        weights_version="v1")
        try:
            handles = [queue.submit(Request(
                codes=(1 + i % 7,) * prompt_len, seed=i,
                sampling=SamplingParams())) for i in range(n_req)]
            vic = rs.replicas[0]
            deadline = time.perf_counter() + 120
            prog = {}
            while time.perf_counter() < deadline:
                rs.step_once()
                if all(h.done() for h in handles):
                    raise AssertionError(
                        f"migrate leg {tag!r}: every request finished "
                        f"before the removal point — decode too short "
                        f"to prove anything")
                prog = vic.engine.progress_snapshot()
                if prog and min(prog.values()) >= min_prog:
                    break
            else:
                raise AssertionError(
                    f"migrate leg {tag!r}: replica 0 never reached "
                    f"{min_prog} tokens in-slot ({prog})")
            pre_tokens = sum(prog.values())
            saved0 = rs.migrated_tokens_saved
            rs.remove_replica(0, drain=drain,
                              reason=f"bench migrate_compare {tag}")
            rs.run_until_idle(max_steps=2_000_000)
            res = [h.result(timeout=120) for h in handles]
            ok = sum(r.ok for r in res)
            if ok != n_req:
                raise AssertionError(
                    f"migrate leg {tag!r} lost requests: {ok}/{n_req} "
                    f"({[r.reason for r in res if not r.ok]})")
            return {
                "requests": n_req, "completed": ok,
                "inflight_at_removal": len(prog),
                "tokens_at_removal": pre_tokens,
                "migrations": rs.migrations,
                "migrate_fallbacks": rs.migrate_fallbacks,
                "tokens_saved": rs.migrated_tokens_saved - saved0,
            }, [None if r.tokens is None else [int(t) for t in r.tokens]
                for r in res]
        finally:
            rs.close()

    migrated, toks_m = leg(True, "migrated")
    replay, toks_r = leg(False, "replay")
    if toks_m != toks_r:
        bad = sum(a != b for a, b in zip(toks_m, toks_r))
        raise AssertionError(
            f"migrated vs replayed tokens diverge on {bad}/{n_req} "
            f"requests — migration must not change WHAT decodes")
    if migrated["migrations"] < 1:
        raise AssertionError(
            f"the drain never migrated a request ({migrated})")
    saved, replayed = migrated["tokens_saved"], \
        replay["tokens_at_removal"]
    if saved < max(1, replayed // 2):
        raise AssertionError(
            f"migration saved {saved} tokens vs {replayed} the replay "
            f"leg re-decoded — under the 50% bar, the move is not "
            f"paying for itself")
    return {
        "migrated": migrated, "replay": replay,
        "tokens_identical": True,
        "saved_vs_replayed_pct": round(100.0 * saved
                                       / max(replayed, 1), 1),
    }


def _serve_gateway_compare(params, cfg, *, num_slots, page_size):
    """The gateway-tier record (docs/SERVING.md 'Gateway tier'), two
    asserted halves:

      * ROUTING — the same repeated-prompt workload (2 prompts x 5
        waves, submission order rotated per wave) through two fresh
        2-cell fleets: prefix-affinity routing vs hash-blind
        least-loaded. Affinity sends a repeated prompt to the cell
        whose PrefixIndex is already warm, so its fleet-wide prefix-hit
        rate must be STRICTLY higher — hash-blind placement follows
        arrival order, which the rotation deliberately scrambles, so
        each prompt's entry lands on whichever cell the tie-break
        picked that wave and the early waves all miss.
      * DEGRADATION — the ``tenant_flood`` fault row drives a synthetic
        abusive tenant (24 requests against an rps=2 bucket) against a
        weight-2 victim on a shared fleet. The contract: the abuser
        sees typed 429s (``tenant_throttled`` with retry-after), every
        ADMITTED request — victim and abuser both — completes OK (zero
        dropped), and the victim's p95 stays within 1.5x its unloaded
        baseline (plus a small additive epsilon for CPU clock jitter,
        recorded in the output).

    Both halves raise AssertionError on violation — CI's serve-gateway
    smoke greps the structured ``"error"`` field like every sibling
    compare leg. The record carries one sample ``gateway_route`` and
    one ``tenant_throttled`` event dict so the smoke can also pin the
    typed-event field names."""
    from dalle_pytorch_tpu.resilience import faults
    from dalle_pytorch_tpu.serve import pages_for
    from dalle_pytorch_tpu.serve.gateway import Gateway
    from dalle_pytorch_tpu.serve.server import InferenceServer
    from dalle_pytorch_tpu.serve.tenancy import TenantTable, \
        TenantThrottled

    slots = min(num_slots, 2)
    prompt_len = min(4, cfg.text_seq_len)

    def fleet(**gw_kwargs):
        # vae_params=None is safe: decode_images=False means the
        # postprocess stage (the only consumer) is never built
        cells = [InferenceServer(params, None, cfg, num_slots=slots,
                                 queue_depth=64, kv="paged",
                                 page_size=page_size,
                                 prefix_cache=True,
                                 decode_images=False,
                                 weights_version="v0").start()
                 for _ in range(2)]
        return Gateway(cells, cfg=cfg, model_version="v0",
                       queue_depth=64,
                       max_prompt_len=cfg.text_seq_len,
                       pages_per_request=pages_for(cfg.seq_len,
                                                   page_size),
                       **gw_kwargs).start()

    # -- leg (a): prefix-affinity vs hash-blind hit rate ---------------
    prompts = [(1,) * prompt_len, (2,) * prompt_len]
    waves = 5

    def routing_leg(affinity, tag):
        gw = fleet(affinity=affinity)
        try:
            for w in range(waves):
                # waves of len(prompts) <= one cell's capacity, so the
                # affine cell is never saturated; the rotation is what
                # makes hash-blind placement drift between cells
                order = prompts if w % 2 == 0 else prompts[::-1]
                handles = [gw.submit(p, seed=0) for p in order]
                for h in handles:
                    r = h.result(timeout=180)
                    if not r.ok:
                        raise AssertionError(
                            f"gateway routing leg {tag!r} wave {w}: "
                            f"{r.status} ({r.reason})")
            st = gw.stats()
            return {
                "hit_rate": st["fleet_prefix_hit_rate"],
                "prefix_hits": st["fleet"]["prefix_hits"],
                "completed": st["fleet"]["completed"],
                "routed": st["routed"], "spills": st["spills"],
            }, gw.events("gateway_route")
        finally:
            gw.close()

    affine, route_events = routing_leg(True, "affinity")
    blind, _ = routing_leg(False, "hash_blind")
    if affine["hit_rate"] <= blind["hit_rate"]:
        raise AssertionError(
            f"prefix-affinity routing must beat hash-blind on the "
            f"repeated-prompt workload: affinity hit rate "
            f"{affine['hit_rate']} vs {blind['hit_rate']}")

    # -- leg (b): tenant_flood degradation contract --------------------
    def p95(lats):
        s = sorted(lats)
        return s[min(int(0.95 * (len(s) - 1) + 0.5), len(s) - 1)]

    tenants = TenantTable.from_json([
        {"name": "victim", "key": "kv", "weight": 2.0},
        {"name": "abuser", "key": "ka", "weight": 1.0, "rps": 2.0}])
    gw = fleet(tenants=tenants)
    victim_prompt = (3,) * prompt_len
    abuser_prompt = (4,) * prompt_len

    def victim_round(n, tag):
        lats = []
        for _ in range(n):
            t0 = time.perf_counter()
            r = gw.generate(victim_prompt, api_key="kv", seed=0,
                            timeout=180)
            if not r.ok:
                raise AssertionError(
                    f"victim request dropped during {tag}: "
                    f"{r.status} ({r.reason})")
            lats.append(time.perf_counter() - t0)
        return lats

    try:
        # compile + warm both prompts outside the timed rounds (the
        # abuser warmup spends one rps token; the flood accounts below)
        gw.generate(victim_prompt, api_key="kv", seed=0, timeout=300)
        gw.generate(abuser_prompt, api_key="ka", seed=0, timeout=300)
        baseline = victim_round(6, "baseline")
        throttled = 0
        sample_throttle = None
        flood_handles = []
        with faults.injected(tenant_flood="abuser",
                             tenant_flood_requests=24):
            flood = faults.gateway_flood()
            for i in range(flood["requests"]):
                try:
                    flood_handles.append(gw.submit(
                        abuser_prompt, api_key="ka", seed=i))
                except TenantThrottled as e:
                    throttled += 1
                    sample_throttle = e.record
            flooded = victim_round(6, "flood")
        if throttled < 1:
            raise AssertionError(
                f"the abuser flood was never throttled "
                f"({len(flood_handles)} admitted) — the rps bucket "
                f"is not enforcing")
        for h in flood_handles:
            r = h.result(timeout=180)
            if not r.ok:
                raise AssertionError(
                    f"an ADMITTED abuser request was dropped "
                    f"({r.status}: {r.reason}) — throttling must "
                    f"happen at admission, never after")
        baseline_p95, flooded_p95 = p95(baseline), p95(flooded)
        # the additive epsilon absorbs CPU-smoke clock jitter on a
        # baseline measured in tens of milliseconds; on a real fleet
        # the 1.5x ratio is the binding term
        eps_s = 0.25
        if flooded_p95 > 1.5 * baseline_p95 + eps_s:
            raise AssertionError(
                f"victim p95 degraded past tolerance under tenant "
                f"flood: {flooded_p95:.3f}s vs 1.5 * "
                f"{baseline_p95:.3f}s + {eps_s}s unloaded")
        flood_rec = {
            "baseline_p95_s": round(baseline_p95, 4),
            "flooded_p95_s": round(flooded_p95, 4),
            "ratio": round(flooded_p95 / max(baseline_p95, 1e-9), 2),
            "epsilon_s": eps_s,
            "victim_completed": len(baseline) + len(flooded),
            "victim_dropped": 0,
            "abuser_admitted": len(flood_handles),
            "abuser_throttled": throttled,
        }
        tstats = gw.tenants.stats()
    finally:
        gw.close()

    return {
        "affinity": affine, "hash_blind": blind,
        "affinity_advantage": round(
            affine["hit_rate"] - blind["hit_rate"], 4),
        "flood": flood_rec,
        "tenants": tstats,
        "sample_events": {
            "gateway_route": route_events[0],
            "tenant_throttled": sample_throttle,
        },
    }


def _serve_mesh_compare(params, cfg, *, mesh_devices, num_slots, n_req,
                        kv, page_size, chunk_steps=8):
    """The mesh-sharded engine record (docs/SERVING.md 'Mesh-sharded
    engine'), three asserted halves:

      * EQUALITY — a fixed seeded burst through the single-device
        engine and the mesh engine must emit byte-identical tokens
        (the partition rules shard no contracted dim, so this is a
        construction guarantee; the bench re-proves it on every run);
      * TAX — single-device vs mesh ms/token at the same offered load
        (the per-layer all-gathers are the cost of fitting at all;
        report-only — on virtual CPU devices the collectives are
        memcpy theater, on real ICI they are the honest number);
      * HBM BUDGET — a modeled per-device budget is chosen BETWEEN the
        config's single-device residency (params + KV pool) and its
        per-shard residency: the config provably does NOT fit one
        device under that budget, DOES fit each mesh shard, and the
        mesh engine then actually serves the full burst with exactly
        one decode compile and zero losses. That is the serving-scale
        claim — models too big for one chip serve from one logical
        engine — in asserted form.
    """
    import jax
    import numpy as np

    from dalle_pytorch_tpu.serve import Request, RequestQueue, \
        SamplingParams
    from dalle_pytorch_tpu.serve.engine import Engine
    from dalle_pytorch_tpu.serve.mesh_engine import MeshEngine, hbm_report
    from dalle_pytorch_tpu.parallel import serve_specs as SS

    devices = jax.devices()
    if len(devices) < mesh_devices:
        raise AssertionError(
            f"--serve_mesh {mesh_devices} needs that many devices, "
            f"have {len(devices)}")
    prompt_len = min(4, cfg.text_seq_len)
    n_load = max(n_req, 2 * num_slots)
    tokens_per_req = cfg.seq_len - prompt_len
    out = {"mesh_devices": mesh_devices, "requests": n_load,
           "tokens_per_request": tokens_per_req}

    def build(mesh):
        queue = RequestQueue(max_depth=max(4 * n_load, 16))
        kw = dict(num_slots=num_slots, chunk_steps=chunk_steps, kv=kv,
                  page_size=page_size if kv == "paged" else 0)
        if mesh:
            eng = MeshEngine(params, cfg, queue,
                             devices=SS.slice_devices(
                                 devices, 0, mesh_devices), **kw)
        else:
            eng = Engine(params, cfg, queue, **kw)
        return eng, queue

    # equality burst: same seeds/knobs through both engines, tokens
    # byte-identical — the acceptance criterion, re-proved per run
    n_eq = 4
    tokens = {}
    for mesh in (False, True):
        eng, queue = build(mesh)
        handles = [queue.submit(Request(
            codes=(1 + i % 5,) * prompt_len, seed=i,
            sampling=SamplingParams())) for i in range(n_eq)]
        eng.run_until_idle()
        results = [h.result(timeout=300) for h in handles]
        bad = [r for r in results if r.status != "ok"]
        if bad:
            # a failed request must surface as ITSELF, not masquerade
            # as a byte-identity mismatch of a None token array
            raise AssertionError(
                f"mesh={mesh}: equality burst had non-ok results: "
                f"{[(r.status, r.reason) for r in bad]}")
        tokens[mesh] = [np.asarray(r.tokens) for r in results]
    mismatches = sum(not np.array_equal(a, b)
                     for a, b in zip(tokens[False], tokens[True]))
    out["token_mismatches"] = mismatches
    if mismatches:
        raise AssertionError(
            f"mesh tokens diverged from single-device on "
            f"{mismatches}/{n_eq} requests — the no-sharded-"
            f"contraction byte-identity contract broke")

    # tax legs: one load point each, same offered load, single-threaded
    # drive, one-compile asserted
    for mesh in (False, True):
        eng, queue = build(mesh)
        warm = queue.submit(Request(codes=(1,) * prompt_len, seed=0,
                                    sampling=SamplingParams()))
        eng.run_until_idle()
        warm.result(timeout=300)
        point = _serve_load_point(eng, queue, 1000.0, n_load, prompt_len)
        if point["completed"] != n_load:
            raise AssertionError(
                f"mesh={mesh}: only {point['completed']}/{n_load} "
                f"completed")
        if eng.decode_traces != 1:
            raise AssertionError(
                f"mesh={mesh}: decode compiled {eng.decode_traces} "
                f"times — the one-compile contract broke")
        leg = {
            "ms_per_token": round(
                1e3 / max(point["tokens_per_s"], 1e-9), 4),
            "throughput_imgs_per_s": point["throughput_imgs_per_s"],
            "decode_compiles": eng.decode_traces,
            "hbm": hbm_report(eng),
        }
        out["mesh" if mesh else "single"] = leg
    single_ms = out["single"]["ms_per_token"]
    out["mesh_tax_pct"] = round(
        100.0 * (out["mesh"]["ms_per_token"] - single_ms)
        / max(single_ms, 1e-9), 1)

    # HBM-budget leg: pick the per-device budget between the modeled
    # single-device residency and the per-shard residency — the config
    # does NOT fit one device, DOES fit each shard — then serve the
    # full burst from the mesh under it
    hbm = out["mesh"]["hbm"]
    if not (hbm["total_bytes_per_shard"] < hbm["total_bytes"]):
        raise AssertionError(
            f"mesh sharded nothing: per-shard {hbm} — heads/depth "
            f"must divide the mesh for the budget leg to mean anything")
    budget = (hbm["total_bytes"] + hbm["total_bytes_per_shard"]) // 2
    out["hbm_budget"] = {
        "device_budget_bytes": int(budget),
        "single_device_bytes": hbm["total_bytes"],
        "per_shard_bytes": hbm["total_bytes_per_shard"],
        "fits_single_device": hbm["total_bytes"] <= budget,
        "fits_mesh_shard": hbm["total_bytes_per_shard"] <= budget,
    }
    assert not out["hbm_budget"]["fits_single_device"]
    assert out["hbm_budget"]["fits_mesh_shard"]
    eng, queue = build(True)
    handles = [queue.submit(Request(codes=(1 + i % 7,) * prompt_len,
                                    seed=i, sampling=SamplingParams()))
               for i in range(n_load)]
    eng.run_until_idle()
    ok = sum(h.result(timeout=300).status == "ok" for h in handles)
    out["hbm_budget"]["completed"] = ok
    out["hbm_budget"]["decode_compiles"] = eng.decode_traces
    if ok != n_load or eng.decode_traces != 1:
        raise AssertionError(
            f"HBM-budget leg broke: {ok}/{n_load} completed, "
            f"{eng.decode_traces} decode compiles")
    return out


def bench_serve(args):
    """Serving-path bench: the continuous-batching engine
    (dalle_pytorch_tpu/serve) under an offered-load sweep, swept over the
    fused-chunk size K (``--serve_chunks``) with the KV layout picked by
    ``--serve_kv`` (dense slot cache, or the paged block-pool — fully
    provisioned here so the K-sweep contracts are layout-independent).
    For each K a fresh engine runs every load point; the record carries
    throughput, p50/p95 end-to-end latency, slot occupancy, reject
    counts, and ``host_round_trips_per_token`` — the number the
    device-resident decode loop exists to drive down (1/(K*occupancy) vs
    the old per-step fetch's 1/occupancy). Contracts are asserted, not
    just measured (docs/SERVING.md methodology): the decode program may
    compile exactly ONCE per engine (shared guards.compile_count), the
    whole sweep runs under ``guards.no_transfers()`` — an implicit
    host<->device transfer anywhere in the steady-state loop fails the
    config with an ``"error"`` field, which CI's serve-perf smoke greps
    for — and the ``kv_budget_compare`` sub-record asserts the paged
    engine sustains MORE concurrent requests than dense under the same
    simulated HBM page budget (``_serve_kv_budget_compare``)."""
    import jax
    import jax.numpy as jnp

    from dalle_pytorch_tpu.models import dalle as D
    from dalle_pytorch_tpu.serve import Request, RequestQueue, \
        SamplingParams
    from dalle_pytorch_tpu.serve.engine import Engine

    from dalle_pytorch_tpu.analysis import guards

    cfg = build_cfg(args.tiny, depth=12 if not args.tiny else 2)
    key = jax.random.PRNGKey(0)
    params = jax.device_put(D.dalle_init(key, cfg, dtype=jnp.bfloat16))

    num_slots = args.serve_slots
    n_req = args.serve_requests
    try:
        loads = [float(x) for x in args.serve_loads.split(",")]
    except ValueError:
        raise ValueError(f"--serve_loads must be comma-separated numbers, "
                         f"got {args.serve_loads!r}")
    if any(rps <= 0 for rps in loads):
        # rps divides the inter-arrival gap; 0 would ZeroDivide
        # mid-sweep after the expensive warmup
        raise ValueError(f"--serve_loads entries must be > 0, got "
                         f"{args.serve_loads!r}")
    try:
        chunk_sweep = [int(k) for k in args.serve_chunks.split(",")]
    except ValueError:
        raise ValueError(f"--serve_chunks must be comma-separated ints, "
                         f"got {args.serve_chunks!r}")
    if any(k < 1 for k in chunk_sweep):
        raise ValueError(f"--serve_chunks entries must be >= 1, got "
                         f"{args.serve_chunks!r}")
    prompt_len = min(4, cfg.text_seq_len)
    errors = []
    kv = args.serve_kv
    paged_attn = args.serve_paged_attn
    if paged_attn == "kernel" and kv != "paged":
        raise ValueError("--serve_paged_attn kernel requires "
                         "--serve_kv paged (the kernel reads the page "
                         "pool through block tables)")
    # default page size: divide the tiny seq exactly so the budget
    # comparison compares equal KV bytes, 16 rows on the real config
    page_size = args.serve_page_size or (8 if args.tiny else 16)

    k_sweep = []
    for k in chunk_sweep:
        # one queue/engine pair per K for the whole load sweep: the
        # fused decode program and the per-bucket prefill programs
        # compile once, ever
        queue = RequestQueue(max_depth=2 * num_slots)
        engine = Engine(params, cfg, queue, num_slots=num_slots,
                        chunk_steps=k, kv=kv,
                        page_size=page_size if kv == "paged" else 0,
                        paged_attn=paged_attn if kv == "paged"
                        else "gather")
        _progress(f"serve: K={k} compiling bucketed prefill + fused "
                  f"{k}-step decode ({num_slots} slots, kv={kv}"
                  + (f"/{paged_attn}" if kv == "paged" else "")
                  + f", seq {cfg.seq_len})")
        with guards.compile_count(lambda: engine.decode_traces, expect=1,
                                  label=f"serve decode program (K={k})",
                                  raise_on_violation=False) as decode_guard:
            # warm the jit cache outside the timed + transfer-guarded
            # region (same discipline as time_steps' warmup)
            h = queue.submit(Request(codes=(1,) * prompt_len, seed=0,
                                     sampling=SamplingParams()))
            engine.run_until_idle()
            h.result(timeout=60)

            results = []
            # steady state is TRANSFER-CLEAN: decode state never leaves
            # the device; the only host reads are the explicit emit-ring
            # harvests the engine counts
            with guards.no_transfers():
                for rps in loads:
                    point = _serve_load_point(engine, queue, rps, n_req,
                                              prompt_len)
                    results.append(point)
                    _progress(f"serve: K={k} rps={rps} done "
                              f"({point['completed']} ok, "
                              f"{point['rejected']} rejected, "
                              f"{point['wall_s']}s)")
        snap = engine.stats()
        entry = {
            "chunk_steps": k, "results": results,
            "decode_compiles": snap["decode_compiles"],
            "prefill_compiles": snap["prefill_compiles"],
            "host_round_trips_per_token":
                snap["host_round_trips_per_token"],
        }
        if decode_guard.error is not None:
            # the one-compile contract IS the point of the fixed-shape
            # slot pool; a recompile mid-sweep is a correctness failure,
            # not noise
            entry["error"] = str(decode_guard.error)
            errors.append(str(decode_guard.error))
        k_sweep.append(entry)

    _progress("serve: dense-vs-paged same-budget concurrency comparison")
    try:
        kv_compare = _serve_kv_budget_compare(
            params, cfg, num_slots=num_slots, page_size=page_size,
            min_requests=args.serve_requests)
    except Exception as e:  # noqa: BLE001 — a wedged compare engine or
        # bad page math must land in the structured "error" field the
        # serve-perf CI leg greps, not torch the whole bench_all JSON
        kv_compare = {"error": f"{type(e).__name__}: {e}"}
        errors.append(str(e))

    _progress("serve: paged-attention gather-vs-kernel comparison")
    try:
        from dalle_pytorch_tpu.serve import kv_pool as _kv_pool
        try:
            _kv_pool.validate_page_size(page_size)
            compare_ps = page_size
        except _kv_pool.PageSizeError:
            # a gather-only page size (e.g. 4) can't feed the kernel —
            # compare at the kernel's tile minimum instead of erroring
            compare_ps = _kv_pool.KERNEL_MIN_PAGE_SIZE
        pa_compare = _serve_paged_attn_compare(
            params, cfg, num_slots=num_slots, page_size=compare_ps)
    except Exception as e:  # noqa: BLE001 — same structured-error
        # contract: the serve-perf CI smoke greps for it
        pa_compare = {"error": f"{type(e).__name__}: {e}"}
        errors.append(str(e))

    _progress("serve: dense-reads vs sparsity-aware reads comparison")
    try:
        sparse_compare = _serve_sparse_reads_compare(
            num_slots=min(num_slots, 2))
    except Exception as e:  # noqa: BLE001 — same structured-error
        # contract: the serve-perf sparse_reads CI leg greps for it
        sparse_compare = {"error": f"{type(e).__name__}: {e}"}
        errors.append(str(e))

    _progress("serve: prefix-cache warm-vs-cold + guided-pair cost "
              "comparison")
    try:
        prefix_compare = _serve_prefix_compare(
            num_slots=min(num_slots, 4))
    except Exception as e:  # noqa: BLE001 — same structured-error
        # contract: the serve-perf prefix_cache CI leg greps for it
        prefix_compare = {"error": f"{type(e).__name__}: {e}"}
        errors.append(str(e))

    spec_compare = None
    if args.serve_speculative:
        _progress(f"serve: eager vs speculative decode comparison "
                  f"(k={args.serve_speculative})")
        try:
            spec_compare = _serve_spec_compare(
                params, cfg, k=args.serve_speculative,
                num_slots=min(num_slots, 2))
        except Exception as e:  # noqa: BLE001 — same structured-error
            # contract: the serve-perf speculative CI leg greps for it
            spec_compare = {"error": f"{type(e).__name__}: {e}"}
            errors.append(str(e))

    replica_compare = None
    if args.replicas > 1:
        _progress(f"serve: {args.replicas}-replica scaling + "
                  f"injected-kill failover comparison")
        try:
            replica_compare = _serve_replica_compare(
                params, cfg, replicas=args.replicas,
                num_slots=num_slots, n_req=n_req, kv=kv,
                page_size=page_size)
        except Exception as e:  # noqa: BLE001 — same structured-error
            # contract as the kv compare: the serve-faults CI smoke
            # greps for it
            replica_compare = {"error": f"{type(e).__name__}: {e}"}
            errors.append(str(e))

    isolation_compare = None
    if args.replicas > 1 and args.isolation == "process":
        _progress(f"serve: thread-vs-process isolation tax + child "
                  f"SIGKILL failover ({args.replicas} replicas)")
        try:
            isolation_compare = _serve_isolation_compare(
                params, cfg, replicas=args.replicas,
                num_slots=num_slots, n_req=n_req, kv=kv,
                page_size=page_size)
        except Exception as e:  # noqa: BLE001 — structured-error
            # contract: the serve-faults process CI leg greps for it
            isolation_compare = {"error": f"{type(e).__name__}: {e}"}
            errors.append(str(e))

    mesh_compare = None
    if args.serve_mesh > 1:
        _progress(f"serve: single-device vs {args.serve_mesh}-device "
                  f"mesh comparison + HBM-budget leg")
        try:
            mesh_compare = _serve_mesh_compare(
                params, cfg, mesh_devices=args.serve_mesh,
                num_slots=num_slots, n_req=n_req, kv=kv,
                page_size=page_size)
        except Exception as e:  # noqa: BLE001 — structured-error
            # contract: the serve-mesh CI smoke greps for it
            mesh_compare = {"error": f"{type(e).__name__}: {e}"}
            errors.append(str(e))

    transport_compare = None
    if args.replicas > 1 and args.isolation == "process" \
            and args.transport == "socket":
        _progress(f"serve: pipe-vs-socket transport tax + connection-"
                  f"reset failover ({args.replicas} replicas)")
        try:
            transport_compare = _serve_transport_compare(
                params, cfg, replicas=args.replicas,
                num_slots=num_slots, n_req=n_req, kv=kv,
                page_size=page_size)
        except Exception as e:  # noqa: BLE001 — structured-error
            # contract: the serve-faults socket CI leg greps for it
            transport_compare = {"error": f"{type(e).__name__}: {e}"}
            errors.append(str(e))

    elastic_compare = None
    if args.serve_elastic:
        _progress("serve: elastic ramp (autoscale scale-out + rolling "
                  "weight upgrade, zero-loss asserted)")
        try:
            elastic_compare = _serve_elastic_compare(
                params, cfg, num_slots=num_slots)
        except Exception as e:  # noqa: BLE001 — structured-error
            # contract: the serve-elastic CI leg greps for it
            elastic_compare = {"error": f"{type(e).__name__}: {e}"}
            errors.append(str(e))

    migration_compare = None
    if args.serve_migrate:
        _progress("serve: live-migration vs replay-from-zero "
                  "comparison (zero-loss + byte-identity asserted)")
        try:
            migration_compare = _serve_migrate_compare(
                params, cfg, num_slots=num_slots, page_size=page_size)
        except Exception as e:  # noqa: BLE001 — structured-error
            # contract: the serve-migrate CI leg greps for it
            migration_compare = {"error": f"{type(e).__name__}: {e}"}
            errors.append(str(e))

    fanout_compare = None
    if args.serve_fanout:
        _progress(f"serve: streaming best-of-{args.serve_fanout} "
                  f"fan-out + COW page bound + preview identity")
        try:
            fanout_compare = _serve_fanout_compare(
                n_samples=args.serve_fanout)
        except Exception as e:  # noqa: BLE001 — structured-error
            # contract: the serve-stream CI leg greps for it
            fanout_compare = {"error": f"{type(e).__name__}: {e}"}
            errors.append(str(e))

    gateway_compare = None
    if args.serve_gateway:
        _progress("serve: gateway tier — affinity-vs-hash-blind "
                  "routing + tenant-flood degradation contract")
        try:
            gateway_compare = _serve_gateway_compare(
                params, cfg, num_slots=num_slots, page_size=page_size)
        except Exception as e:  # noqa: BLE001 — structured-error
            # contract: the serve-gateway CI leg greps for it
            gateway_compare = {"error": f"{type(e).__name__}: {e}"}
            errors.append(str(e))

    best = k_sweep[-1]["results"][-1]
    record = {
        "metric": "serve engine offered-load sweep (device-resident "
                  "fused-chunk decode)"
                  if not args.tiny else "tiny serve sweep",
        "value": best["throughput_imgs_per_s"],
        "unit": f"imgs/sec at highest load, K={chunk_sweep[-1]}",
        "vs_baseline": None,
        "num_slots": num_slots, "seq_len": cfg.seq_len,
        "prompt_len": prompt_len, "chunk_sweep": chunk_sweep,
        "kv": kv, "paged_attn": paged_attn,
        "k_sweep": k_sweep, "transfer_clean": True,
        "kv_budget_compare": kv_compare,
        "paged_attn_compare": pa_compare,
        "sparse_reads_compare": sparse_compare,
        "prefix_compare": prefix_compare,
        "devices": len(jax.devices()), "backend": jax.default_backend(),
    }
    if mesh_compare is not None:
        record["mesh_compare"] = mesh_compare
    if replica_compare is not None:
        record["replica_compare"] = replica_compare
    if isolation_compare is not None:
        record["isolation_compare"] = isolation_compare
    if transport_compare is not None:
        record["transport_compare"] = transport_compare
    if elastic_compare is not None:
        record["elastic_compare"] = elastic_compare
    if spec_compare is not None:
        record["spec_compare"] = spec_compare
    if migration_compare is not None:
        record["migration_compare"] = migration_compare
    if fanout_compare is not None:
        record["fanout_compare"] = fanout_compare
    if gateway_compare is not None:
        record["gateway_compare"] = gateway_compare
    if errors:
        record["error"] = "; ".join(errors)
    return record


def bench_all(args):
    """Every BASELINE config in one combined JSON object. The north star is
    the top level; each config (north included) records its result or its
    error — one broken config must not hide the others' numbers, and
    ``main`` exits non-zero if any config carries an ``error``."""
    try:
        out = bench_north(args)
    except Exception as e:
        out = {"metric": "bench failed: north", "value": None, "unit": None,
               "vs_baseline": None, "error": f"{type(e).__name__}: {e}",
               "trace": traceback.format_exc(limit=3)}
    out["configs"] = {}
    for name, fn in (("vae", bench_vae), ("rev", bench_rev),
                     ("sparse", bench_sparse), ("moe", bench_moe),
                     ("kernels", bench_kernels), ("serve", bench_serve)):
        _progress(f"config {name} ...")
        t0 = time.perf_counter()
        try:
            out["configs"][name] = fn(args)
        except Exception as e:
            out["configs"][name] = {
                "error": f"{type(e).__name__}: {e}",
                "trace": traceback.format_exc(limit=3)}
        out["configs"][name]["config_wall_s"] = round(
            time.perf_counter() - t0, 1)
        _progress(f"config {name} done in "
                  f"{out['configs'][name]['config_wall_s']}s")
    return out


def errored_configs(out: dict) -> list:
    """Names of the configs in a bench result that carry an ``error``
    (``north`` for the top level) — what decides the exit code."""
    bad = ["north"] if out.get("error") else []
    return bad + [name for name, rec in out.get("configs", {}).items()
                  if rec.get("error")]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--tiny", action="store_true",
                    help="tiny model for CPU smoke runs (not a benchmark)")
    ap.add_argument("--config", default="all",
                    choices=["all", "north", "vae", "rev", "sparse", "moe",
                             "kernels", "serve"])
    ap.add_argument("--attn", default="auto",
                    choices=["auto", "xla", "flash", "flash_pallas",
                             "flash_pallas_fused"],
                    help="flash_pallas = flash forward + Pallas backward "
                         "kernels")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--warmup", type=int, default=3)
    ap.add_argument("--batch", type=int, default=0)
    ap.add_argument("--gen_reps", type=int, default=5)
    ap.add_argument("--loss_chunk", type=int, default=None,
                    help="chunked-CE head size for the north config "
                         "(0 = dense; default: the committed tuned value, "
                         "else dense)")
    ap.add_argument("--remat", default=None,
                    choices=["none", "save_ln", "dots", "full"],
                    help="layer-body rematerialization for the north config "
                         "('save_ln' = drop only the f32 layernorm saves; "
                         "'dots' = recompute vector work only, matmul "
                         "outputs stay saved; default: the committed tuned "
                         "value, else none)")
    ap.add_argument("--no_gen", action="store_true",
                    help="skip the generate-latency half")
    ap.add_argument("--gen_quant", action="store_true",
                    help="also time the sampler with int8-quantized "
                         "linears + vocab head (gen_int8_* fields; "
                         "ops/quant.py)")
    ap.add_argument("--gen_batches", default="1",
                    help="comma list of sampler batch sizes; the first is "
                         "the headline gen_* fields, extras emit "
                         "gen_b{N}_* (batched decode amortizes the "
                         "per-token weight reads the reference's "
                         "re-forward sampler cannot)")
    ap.add_argument("--serve_slots", type=int, default=4,
                    help="bench_serve: decode slot-pool size (the fixed "
                         "batch the one compiled program advances)")
    ap.add_argument("--serve_requests", type=int, default=12,
                    help="bench_serve: requests per offered-load point")
    ap.add_argument("--serve_loads", default="2,16",
                    help="bench_serve: comma list of offered loads "
                         "(requests/sec) — at least two points for the "
                         "latency/throughput curve")
    ap.add_argument("--serve_chunks", default="1,8,32",
                    help="bench_serve: comma list of fused-chunk sizes K "
                         "(decode steps per device program / emitted "
                         "tokens per host round-trip) — K=1 is the "
                         "per-step-fetch baseline the device-resident "
                         "loop is measured against")
    ap.add_argument("--serve_kv", default="dense",
                    choices=["dense", "paged"],
                    help="bench_serve: KV layout for the K-sweep engine "
                         "(the dense-vs-paged budget comparison always "
                         "runs; CI's serve-perf matrix runs one leg per "
                         "layout)")
    ap.add_argument("--serve_paged_attn", default="gather",
                    choices=["gather", "kernel"],
                    help="bench_serve: paged K/V read impl for the "
                         "K-sweep engine (kernel = the Pallas ragged "
                         "paged-attention kernel; requires --serve_kv "
                         "paged). The gather-vs-kernel ms/token + "
                         "read-bytes comparison (paged_attn_compare) "
                         "always runs — asserted on real TPU, "
                         "report-only under interpret mode on CPU")
    ap.add_argument("--serve_page_size", type=int, default=0,
                    help="bench_serve: KV page size for paged engines "
                         "(0 = 8 rows under --tiny so pages divide the "
                         "tiny seq exactly, else 16)")
    ap.add_argument("--serve_mesh", type=int, default=0,
                    help="bench_serve: also run the mesh_compare "
                         "record at this many devices per engine — "
                         "byte-identical tokens single-vs-mesh "
                         "asserted, ms/token both legs, and the "
                         "HBM-budget leg: a modeled per-device budget "
                         "the config does NOT fit on one device but "
                         "DOES fit per mesh shard, served end-to-end "
                         "with one decode compile and zero losses "
                         "(docs/SERVING.md 'Mesh-sharded engine')")
    ap.add_argument("--replicas", type=int, default=1,
                    help="bench_serve: also run the replica-set "
                         "comparison at this many supervised engines "
                         "behind one queue — asserts N-replica "
                         "throughput beats 1-replica at the same "
                         "offered load (transfer-clean, one decode "
                         "compile per replica) and that an injected "
                         "mid-sweep replica kill completes every "
                         "request via failover replay")
    ap.add_argument("--isolation", choices=("thread", "process"),
                    default="thread",
                    help="bench_serve with --replicas N: 'process' "
                         "adds the isolation-tax leg — the same burst "
                         "through thread-isolated vs child-process "
                         "replicas (ms/token + measured IPC harvest "
                         "lag, so the isolation cost is a tracked "
                         "number) — and a hard-failover leg: a REAL "
                         "SIGKILL of a child replica mid-sweep must "
                         "complete every request via shadow-reclaim "
                         "replay (docs/SERVING.md 'Process "
                         "isolation')")
    ap.add_argument("--serve_elastic", action="store_true",
                    help="bench_serve: run the elastic_compare leg — "
                         "an offered-load ramp through a fleet that "
                         "reshapes mid-sweep: the autoscaler adds a "
                         "third replica under the burst, p95 recovers "
                         "post-scale, a rolling weight upgrade cycles "
                         "every replica to a second generation with "
                         "traffic in flight, and the idle ramp-down "
                         "scales back in — zero lost requests and "
                         "per-phase weights_version counts asserted "
                         "(docs/SERVING.md 'Elastic fleet')")
    ap.add_argument("--serve_speculative", type=int, default=0,
                    metavar="K",
                    help="bench_serve: run the spec_compare leg — eager "
                         "vs draft-and-verify speculative decode (K "
                         "drafted tokens per round through a shallow "
                         "depth//4 draft head, one K-wide batched "
                         "verify through the full model) over the same "
                         "seeded burst; zero token mismatches and one "
                         "decode compile per leg always asserted, the "
                         ">=2x acceptance-weighted gen_ms_per_token "
                         "win asserted on real TPU when the (K, draft "
                         "depth) pair can mathematically reach it "
                         "(docs/SERVING.md 'Speculative decode')")
    ap.add_argument("--serve_migrate", action="store_true",
                    help="bench_serve: run the migration_compare leg — "
                         "two identical 2-replica paged runs retiring "
                         "replica 0 mid-stream, one via live KV "
                         "migration (the survivor finishes each moved "
                         "request without re-decoding a token), one "
                         "via the replay-from-zero fallback; zero "
                         "losses both legs, byte-identical tokens "
                         "across legs, and migrated_tokens_saved >= "
                         "50% of what replay re-decoded, all asserted "
                         "(docs/SERVING.md 'Live migration & "
                         "disaggregated roles')")
    ap.add_argument("--serve_fanout", type=int, default=0,
                    help="bench_serve: run the fanout_compare leg with "
                         "best-of-N groups (0 = off) — one "
                         "submit(n_samples=N, stream=True) call must "
                         "complete all N CLIP-ranked samples with a "
                         "lifetime page peak <= 1 prompt span + N "
                         "generation spans (the COW bound), every "
                         "sample's SSE token stream byte-identical to "
                         "a standalone sample_seed run, the final "
                         "preview frame bit-equal to the result image, "
                         "and image_seq_len_override a causal prefix "
                         "of the full-resolution run, all asserted "
                         "(docs/SERVING.md 'Streaming, fan-out & "
                         "variable resolution')")
    ap.add_argument("--serve_gateway", action="store_true",
                    help="bench_serve: run the gateway_compare leg — "
                         "two 2-cell fleets route the same repeated-"
                         "prompt workload with prefix-affinity vs "
                         "hash-blind least-loaded (affinity's fleet-"
                         "wide prefix-hit rate must be strictly "
                         "higher), then the tenant_flood fault row "
                         "drives an abusive tenant against a weight-2 "
                         "victim on a shared fleet: typed 429s for the "
                         "abuser, zero dropped requests, victim p95 "
                         "within 1.5x its unloaded baseline, all "
                         "asserted (docs/SERVING.md 'Gateway tier')")
    ap.add_argument("--transport", choices=("pipe", "socket"),
                    default="pipe",
                    help="bench_serve with --isolation process: "
                         "'socket' adds the transport-tax leg — the "
                         "same burst with frames over a duplex pipe vs "
                         "dial-back TCP (ms/token + measured IPC lag "
                         "per leg, socket_tax_pct) — and a network-"
                         "fault leg: an injected connection reset that "
                         "tears a frame mid-stream must fence on a "
                         "typed protocol error and complete every "
                         "request via shadow-reclaim replay "
                         "(docs/SERVING.md 'Host isolation & socket "
                         "transport')")
    args = ap.parse_args()
    if args.gen_quant and args.no_gen:
        ap.error("--gen_quant needs the generate half; drop --no_gen")
    # validate BEFORE the expensive train half; dedup preserving order
    try:
        batches = [int(b) for b in args.gen_batches.split(",")]
    except ValueError:
        ap.error(f"--gen_batches must be comma-separated ints, got "
                 f"{args.gen_batches!r}")
    if any(b < 1 for b in batches):
        ap.error("--gen_batches entries must be >= 1")
    args.gen_batches = list(dict.fromkeys(batches))

    # --tiny is a CPU smoke of the harness, not a benchmark: pin the CPU
    # platform before jax comes up
    if args.tiny:
        os.environ["JAX_PLATFORMS"] = "cpu"

    import jax

    from dalle_pytorch_tpu.utils.device import (describe_device,
                                                enable_compile_cache)
    enable_compile_cache()
    device = describe_device()
    _progress(f"device: {device}, jax {jax.__version__}")
    if not args.tiny and device["platform"] != "tpu":
        # a chip measurement that found no chip: no number, no record
        print(f"bench.py: jax found no TPU (got {device}); a run without "
              f"--tiny measures the chip and refuses anything else",
              file=sys.stderr)
        sys.exit(1)

    try:
        out = {"all": bench_all, "north": bench_north, "vae": bench_vae,
               "rev": bench_rev, "sparse": bench_sparse, "moe": bench_moe,
               "kernels": bench_kernels,
               "serve": bench_serve}[args.config](args)
    except Exception as e:
        _emit({"metric": f"bench failed: {args.config}", "value": None,
               "unit": None, "vs_baseline": None, "device": device,
               "error": f"{type(e).__name__}: {e}",
               "trace": traceback.format_exc(limit=5)}, code=1)
    out["device"] = device
    bad = errored_configs(out)
    if bad:
        _progress(f"errors in: {bad}")
    _emit(out, code=1 if bad else 0)


if __name__ == "__main__":
    main()

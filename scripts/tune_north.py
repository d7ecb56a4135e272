"""North-star tuning sweep: measure train
tokens/sec/chip and MFU for the depth-12 dim-512 DALLE across attention
impls and batch sizes on the real chip, host-synced timing. Prints one JSON
line per point plus a best-config summary; use it to pick bench defaults.

Run: python scripts/tune_north.py [--steps N]
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def cfg_key(r):
    """Identity of a sweep point: the full tunable tuple. Records written
    before a dimension existed default to the value those runs actually
    used (e.g. pre-remat records ran remat='none')."""
    return (r.get("attn"), r.get("batch"), r.get("loss_chunk"),
            r.get("heads", 8), r.get("dim_head", 64),
            r.get("remat", "none"), r.get("reversible", False),
            r.get("flash_block_q", 128), r.get("flash_block_k", 128))


def merge_tune_payload(prev, results, backend="tpu"):
    """Fold this run's ``results`` into the previously committed payload
    (bench.merge_keyed_records: latest measurement wins per cfg_key,
    foreign-backend payloads discarded). ``best`` is recomputed over the
    MERGED set, so a prior winner survives until beaten — but a
    re-measurement of that same config replaces its number (a noisy best
    is correctable, never pinned forever)."""
    from bench import merge_keyed_records
    merged = merge_keyed_records(prev, results, cfg_key, backend)
    best = max(merged, key=lambda r: r["tokens_sec_chip"])
    return {"best": best, "results": merged, "backend": backend}


def _write_merged(results, out=None):
    """Merge ``results`` into docs/TUNE_NORTH.json (latest-wins per config,
    best recomputed over the merged set) and return the path. ``out``
    overrides the destination (tests)."""
    out = out or os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "docs", "TUNE_NORTH.json")
    from bench import atomic_write_json
    prev = None
    try:
        with open(out) as f:
            prev = json.load(f)
    except (OSError, ValueError):
        pass
    return atomic_write_json(out, merge_tune_payload(prev, results))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=15)
    ap.add_argument("--warmup", type=int, default=3)
    ap.add_argument("--attns", default="xla,flash")
    ap.add_argument("--batches", default="8,16,32")
    ap.add_argument("--loss_chunks", default="0",
                    help="comma list; 0 = dense CE head")
    ap.add_argument("--head_cfgs", default="8x64",
                    help="comma list of headsxdim_head splits of the 512 "
                         "inner dim (e.g. '8x64,4x128'; 4x128 fills the "
                         "MXU's 128-wide contraction)")
    ap.add_argument("--remats", default="none",
                    help="comma list of layer-body remat modes "
                         "('none,dots,full'); 'full' trades ~1/3 more "
                         "FLOPs for per-layer activation memory, 'dots' "
                         "recomputes only vector work (matmul outputs stay "
                         "saved, ~2/3 of activation bytes reclaimed at "
                         "near-zero FLOP cost) — both unlock batches that "
                         "OOM a 16G v5e chip un-rematerialized")
    ap.add_argument("--flash_blocks", default="128x128",
                    help="comma list of flash-kernel block_q x block_k tile "
                         "sizes (e.g. '128x128,256x256,128x256'); only "
                         "affects attn impls with a flash forward")
    ap.add_argument("--reversibles", default="0",
                    help="comma list of 0/1: run the reversible engine as a "
                         "sweep dimension (O(1) activation memory by "
                         "inversion instead of recompute-by-checkpoint; "
                         "measured FASTER than the sequential stack at "
                         "batch 8 on 2026-07-30: 110.2k vs 105.2k tok/s)")
    args = ap.parse_args()

    import jax

    import bench
    from bench import (_bf16_peak, build_cfg, dalle_train_flops_per_token,
                       setup_train, time_steps)
    from dalle_pytorch_tpu.parallel import make_mesh
    from dalle_pytorch_tpu.utils.device import enable_compile_cache

    enable_compile_cache()

    n_dev = len(jax.devices())
    mesh = make_mesh({"dp": n_dev})
    peak = _bf16_peak()
    results = []
    for hc in args.head_cfgs.split(","):
      heads, dim_head = (int(v) for v in hc.split("x"))
      for remat in args.remats.split(","):
       for rev in (bool(int(r)) for r in args.reversibles.split(",")):
        if rev and remat != "none":
            # the reversible engine's early-return branch never reaches the
            # remat logic (transformer.py): rev x remat=full would re-time
            # a byte-identical config under a false label
            continue
        for attn in args.attns.split(","):
         for i_fb, fb in enumerate(args.flash_blocks.split(",")):
          if not attn.startswith("flash") and i_fb > 0:
              continue                  # block sizes don't affect xla attn
          bq, bk = ((int(v) for v in fb.split("x"))
                    if attn.startswith("flash") else (128, 128))
          for chunk in (int(c) for c in args.loss_chunks.split(",")):
           for batch in (int(b) for b in args.batches.split(",")):
            cfg = build_cfg(False, depth=12, attn_impl=attn,
                            loss_chunk=chunk, heads=heads,
                            dim_head=dim_head, remat=remat,
                            reversible=rev, flash_block_q=bq,
                            flash_block_k=bk)
            t0 = time.perf_counter()   # duration math — not wall-clock
            try:
                step, params, opt_state, data, key = setup_train(
                    cfg, batch, mesh)
                dt, loss, _ = time_steps(step, params, opt_state, data, key,
                                         args.warmup, args.steps)
            except Exception as e:
                msg = f"{type(e).__name__}: {e}"
                kind = bench.classify_error_kind(msg)
                print(json.dumps({"attn": attn, "batch": batch,
                                  "heads": heads, "dim_head": dim_head,
                                  "loss_chunk": chunk, "remat": remat,
                                  "reversible": rev,
                                  "flash_block_q": cfg.flash_block_q,
                                  "flash_block_k": cfg.flash_block_k,
                                  "kind": kind, "error": msg[:300]}),
                      flush=True)
                continue
            tps = args.steps * batch * cfg.seq_len / dt / n_dev
            mfu = tps * dalle_train_flops_per_token(cfg) / peak
            rec = {"attn": attn, "batch": batch,
                   "batch_per_chip": batch // n_dev, "loss_chunk": chunk,
                   "heads": heads, "dim_head": dim_head, "remat": remat,
                   "reversible": rev,
                   "flash_block_q": cfg.flash_block_q,
                   "flash_block_k": cfg.flash_block_k,
                   "tokens_sec_chip": round(tps, 1), "mfu": round(mfu, 4),
                   "loss": round(loss, 4),
                   "setup_s": round(time.perf_counter() - t0 - dt, 1)}
            results.append(rec)
            print(json.dumps(rec), flush=True)
            # flush the merged record NOW: a later kill (the call's time
            # limit) must not cost the points already measured. bench.py reads
            # this as its north-config defaults (bench_north); committing
            # it is how a sweep's winner becomes the recorded config.
            # Successive sweeps only ever IMPROVE the record: merge keeps
            # the existing best until beaten.
            if jax.default_backend() == "tpu":
                _write_merged(results)

    if results:
        best = max(results, key=lambda r: r["tokens_sec_chip"])
        print(json.dumps({"best": best}), flush=True)
        if jax.default_backend() == "tpu":
            print(json.dumps({"wrote": _write_merged(results)}), flush=True)


if __name__ == "__main__":
    main()

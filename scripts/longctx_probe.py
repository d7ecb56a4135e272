"""Long-context attention probe: fwd+bwd throughput of the transformer
stack at sequence lengths past the flagship's 1280, xla vs flash.

The point (SURVEY §5.7 build note; VERDICT r3 calls long-context
first-class): the flash kernel's claim to exist is MEMORY — it never
materializes the (n, n) score matrix, so it keeps training at context
lengths where the xla path's quadratic buffers exhaust a 16G chip. This
probe measures both impls at growing seq lengths and records, for each
point, tokens/sec or the classified OOM — the committed evidence for
that crossover (docs/LONGCTX.json, merged incrementally like
TUNE_NORTH).

Run: python scripts/longctx_probe.py [--seqs 2560,5120,10240]
     [--impls xla,flash] [--depth 2] [--batch 1] [--steps 5]
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def point_key(r):
    return (r.get("impl"), r.get("seq"), r.get("depth"), r.get("batch"))


def merge_longctx_payload(prev, results, backend="tpu"):
    """Latest-wins merge per (impl, seq, depth, batch) via
    bench.merge_keyed_records (same discipline as TUNE_NORTH), sorted for
    a stable committed diff."""
    from bench import merge_keyed_records
    merged = merge_keyed_records(prev, results, point_key, backend)
    return {"results": sorted(merged, key=lambda r: (r["impl"], r["seq"])),
            "backend": backend}


def _write_merged(results, out=None):
    from bench import atomic_write_json
    out = out or os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "docs", "LONGCTX.json")
    prev = None
    try:
        with open(out) as f:
            prev = json.load(f)
    except (OSError, ValueError):
        pass
    return atomic_write_json(out, merge_longctx_payload(prev, results))


def run_point(impl, seq, depth, batch, steps, warmup):
    """tokens/sec for fwd+bwd through a depth-layer stack at (batch, seq),
    or raises (caller classifies OOM vs error).

    ``impl`` 'xla'/'flash' compare the SAME dense attention (the memory
    crossover); 'sparse_windowed' runs the VariableSparsity stack via the
    windowed decomposition instead — a different (sparse) attention
    function, recorded as the long-context capability of the sparse
    training path, not as a dense-attention comparison point."""
    import jax
    import jax.numpy as jnp

    from dalle_pytorch_tpu.ops.transformer import (TransformerConfig,
                                                   transformer_apply,
                                                   transformer_init)
    if impl == "sparse_windowed":
        cfg = TransformerConfig(dim=512, depth=depth, seq_len=seq,
                                causal=True, sparse_attn=True,
                                sparse_impl="windowed")
    else:
        cfg = TransformerConfig(dim=512, depth=depth, seq_len=seq,
                                attn_impl=impl, causal=True)
    params = transformer_init(jax.random.PRNGKey(0), cfg, jnp.bfloat16)
    x = jax.random.normal(jax.random.PRNGKey(1), (batch, seq, 512),
                          jnp.bfloat16)

    def loss(p, x):
        return transformer_apply(p, x, cfg=cfg).astype(jnp.float32).mean()

    step = jax.jit(jax.grad(loss))
    from bench import _fetch
    g = None
    for _ in range(max(warmup, 1)):
        g = step(params, x)
    _fetch(jax.tree.leaves(g)[0])
    t0 = time.perf_counter()
    for _ in range(steps):
        g = step(params, x)
    _fetch(jax.tree.leaves(g)[0])
    dt = time.perf_counter() - t0
    return steps * batch * seq / dt


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--seqs", default="2560,5120,10240")
    ap.add_argument("--impls", default="xla,flash")
    ap.add_argument("--depth", type=int, default=2)
    ap.add_argument("--batch", type=int, default=1)
    ap.add_argument("--steps", type=int, default=5)
    ap.add_argument("--warmup", type=int, default=2)
    args = ap.parse_args()

    import jax

    import bench
    from dalle_pytorch_tpu.utils.device import enable_compile_cache

    enable_compile_cache()

    results = []
    # seq-major so each length yields its xla-vs-flash pair together — a
    # run killed at its time limit still leaves comparable points
    for seq in (int(s) for s in args.seqs.split(",")):
        for impl in args.impls.split(","):
            rec = {"impl": impl, "seq": seq, "depth": args.depth,
                   "batch": args.batch}
            try:
                tps = run_point(impl, seq, args.depth, args.batch,
                                args.steps, args.warmup)
                rec["tokens_sec"] = round(tps, 1)
            except Exception as e:
                msg = f"{type(e).__name__}: {e}"
                rec["kind"] = bench.classify_error_kind(msg)
                rec["error"] = msg[:300]
            results.append(rec)
            print(json.dumps(rec), flush=True)
            if jax.default_backend() == "tpu":
                _write_merged(results)

    if results and jax.default_backend() == "tpu":
        print(json.dumps({"wrote": _write_merged(results)}), flush=True)


if __name__ == "__main__":
    main()

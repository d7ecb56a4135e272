"""chip_smoke.py — the main path, end to end, on the chip.

The quickest proof that the system still starts on a TPU, through the
entry points a user would call, at the widths ``NORTH`` below
(DiscreteVAE 256 px / 3 layers / 2048 tokens / codebook 512 /
hidden 64; DALLE dim 512, depth 12, 8 x 64 heads, 256 text + 1024 image
positions, 10000 text tokens, bf16 parameters), weights random from a
seed. In ONE process (a chip belongs to one process):

  data        seeded synthetic image/caption folder
  train_vae   ``cli.train_vae.main``, a few steps, checkpoint written
  train_dalle ``cli.train_dalle.main --attn_impl flash``, a few steps,
              then again RESUMED from its checkpoint; losses finite
  serve       the server as ``cli.serve`` starts it, ``--kv paged``:
              POST /generate plain, best-of-N streamed (COW fork +
              previews), two concurrent; /stats and /healthz contracts
  serve_kernel the same requests with ``--paged_attn kernel``
  kernels     every Pallas variant a public flag reaches, compiled
              (``interpret=False``) at the north widths vs its XLA oracle
  sync        the same N train steps timed ending in
              ``block_until_ready`` and in a host fetch must agree
  multichip   (more than one device) every device holds its share; one
              tp x fsdp train step; one ``--mesh_devices`` engine answer

Run as a program it takes no option and cannot pass without a TPU: it
exits non-zero, printing no result, when jax finds none. Each phase
prints its wall time split into compile and run, and the device's peak
bytes; any failed phase makes the exit code non-zero. The last line of
stdout is ``{"ok": true, "device": {...}}`` with the device as jax
reports it. The phases are plain functions of ``Widths`` so a CPU test
drives them at tiny widths with the kernels interpreted
(tests/test_chip_smoke.py).
"""

from __future__ import annotations

import contextlib
import dataclasses
import json
import os
import shutil
import sys
import tempfile
import threading
import time
import traceback
import urllib.error
import urllib.request

T0 = time.perf_counter()
# the contract is exit 0 within 1200 s; past this the run has failed
# anyway, and a phase that hangs must not hold the chip for good
DEADLINE_S = 1170.0


@dataclasses.dataclass(frozen=True)
class Widths:
    """Every size the phases depend on. ``batch``/``n_images`` are PER
    DEVICE; the phases scale them by the device count."""
    image_size: int = 256
    vae_layers: int = 3
    num_tokens: int = 2048
    codebook_dim: int = 512
    hidden_dim: int = 64
    dim: int = 512
    depth: int = 12
    heads: int = 8
    dim_head: int = 64
    text_seq_len: int = 256
    num_text_tokens: int = 10000
    param_dtype: str = "bfloat16"
    batch: int = 8                 # per device
    steps: int = 2                 # train steps per epoch
    num_slots: int = 4
    chunk_steps: int = 8
    page_size: int = 16            # the engine's default
    n_samples: int = 3             # best-of-N group size
    sync_steps: int = 5
    # block_until_ready and host-fetch timings of the same steps must
    # agree this closely (an early-returning sync is off by orders of
    # magnitude; the tiny CPU steps are too short to time any tighter)
    sync_tolerance: float = 0.2
    f32_depth: int = 2             # depth of the f32 kernel-vs-gather engines
    f32_tokens: int = 64           # image tokens they decode

    @property
    def image_seq_len(self) -> int:
        return (self.image_size // 2 ** self.vae_layers) ** 2

    @property
    def seq_len(self) -> int:
        return self.text_seq_len + self.image_seq_len


NORTH = Widths()
TINY = Widths(image_size=16, vae_layers=2, num_tokens=24, codebook_dim=16,
              hidden_dim=8, dim=16, depth=2, heads=2, dim_head=8,
              text_seq_len=8, num_text_tokens=64, param_dtype="float32",
              batch=2, steps=2, num_slots=4, chunk_steps=4, page_size=8,
              n_samples=2, sync_steps=4, sync_tolerance=1.0, f32_depth=2,
              f32_tokens=8)

# kernel-vs-oracle relative error bound: max |a-b| / max |b| under 2%
# (MXU operands round through bf16, so ~0.5% is by construction, and a
# wrong mask/tile/stat blows past 100%)
KERNEL_RELDIFF = 2e-2
# the paged kernel against the gather oracle on f32 parameters with exact
# matmuls — tests/test_paged_attention.py's tolerance
PAGED_RTOL, PAGED_ATOL = 2e-5, 2e-6


# ---------------------------------------------------------------------------
# phase bookkeeping
# ---------------------------------------------------------------------------

class Report:
    """Per-phase wall/compile/run seconds, peak device bytes and outcome.
    Compile seconds are jax's own backend-compile durations (cache
    retrieval included), summed over every thread of the process: the
    benchmark's listener."""

    def __init__(self):
        from benchmark.harness import CompileListener
        self.phases = []
        self.failed = []
        self._compiles = CompileListener()

    @staticmethod
    def peak_bytes():
        import jax
        out = []
        for d in jax.devices():
            stats = d.memory_stats() or {}
            out.append(stats.get("peak_bytes_in_use"))
        return out

    def run(self, name: str, fn, needs=()):
        """Run one phase, ``fn(info)``; -> its value, or None when it (or
        a phase it needs) failed. A failure is recorded and the run goes
        on to the phases that do not depend on it."""
        rec = {"phase": name}
        missing = [n for n in needs if n in self.failed]
        if missing:
            rec.update(ok=False, error=f"skipped: needs {missing}")
            self.failed.append(name)
            self.phases.append(rec)
            print(json.dumps(rec), flush=True)
            return None
        before = self._compiles.snapshot()
        t0 = time.perf_counter()
        info, value = {}, None
        try:
            value = fn(info)
            rec["ok"] = True
        except Exception as e:  # noqa: BLE001 — report every phase
            rec.update(ok=False, error=f"{type(e).__name__}: {e}"[:2000])
            traceback.print_exc(file=sys.stderr)
            self.failed.append(name)
        wall = time.perf_counter() - t0
        after = self._compiles.snapshot()
        compile_s = after["compile_s"] - before["compile_s"]
        rec.update(wall_s=round(wall, 2), compile_s=round(compile_s, 2),
                   run_s=round(max(wall - compile_s, 0.0), 2),
                   cache_hits=after["hits"] - before["hits"],
                   cache_misses=after["misses"] - before["misses"],
                   peak_bytes_in_use=self.peak_bytes(),
                   t_s=round(time.perf_counter() - T0, 1), **info)
        self.phases.append(rec)
        print(json.dumps(rec), flush=True)
        return value


def check(cond, msg: str) -> None:
    if not cond:
        raise AssertionError(msg)


# ---------------------------------------------------------------------------
# data
# ---------------------------------------------------------------------------

COLORS = ("red", "blue", "green", "gray")


def make_dataset(root: str, w: Widths, n_images: int, seed: int = 0) -> None:
    """Seeded synthetic image/caption folder in the CLIs' data contract:
    ``imagedata/0/*.png`` + a captions-only corpus + 'file : caption'
    pairs. Structured content so the models have something to fit."""
    import numpy as np
    from PIL import Image
    rng = np.random.default_rng(seed)
    img_dir = os.path.join(root, "imagedata", "0")
    os.makedirs(img_dir)
    s = w.image_size
    q = max(s // 4, 1)
    only, pairs = [], []
    for i in range(n_images):
        arr = np.zeros((s, s, 3), np.uint8)
        arr[:, :, i % 3] = 255
        x, y = rng.integers(0, s - q + 1, 2)
        arr[y:y + q, x:x + q] = rng.integers(0, 255, (q, q, 3))
        name = f"img{i}.png"
        Image.fromarray(arr).save(os.path.join(img_dir, name))
        cap = f"a {COLORS[i % 4]} square"
        only.append(cap + "\n")
        pairs.append(f"{name} : {cap}\n")
    with open(os.path.join(root, "only.txt"), "w") as f:
        f.writelines(only)
    with open(os.path.join(root, "pairs.txt"), "w") as f:
        f.writelines(pairs)
    for d in ("models", "results"):
        os.makedirs(os.path.join(root, d))


def _losses(metrics_path: str):
    """(per-step losses, per-epoch avg losses) from a CLI metrics JSONL."""
    steps, epochs = [], []
    with open(metrics_path) as f:
        for line in f:
            rec = json.loads(line)
            if rec.get("event") == "checkpoint":
                epochs.append(rec["avg_loss"])
            elif "loss" in rec and "step" in rec:
                steps.append(rec["loss"])
    return steps, epochs


def _common_args(root: str, w: Widths, n_dev: int, metrics: str):
    return ["--dataPath", os.path.join(root, "imagedata"),
            "--imageSize", str(w.image_size),
            "--batchSize", str(w.batch * n_dev),
            "--models_dir", os.path.join(root, "models"),
            "--results_dir", os.path.join(root, "results"),
            "--metrics", os.path.join(root, metrics),
            "--log_interval", "1", "--n_epochs", "1",
            # an implicit host<->device transfer in the step body raises
            # at its site (on the CPU backend the guard sees nothing)
            "--guard_transfers"]


# ---------------------------------------------------------------------------
# trainer phases
# ---------------------------------------------------------------------------

def phase_train_vae(root: str, w: Widths, n_dev: int, info: dict) -> None:
    import math

    from dalle_pytorch_tpu import checkpoint as ckpt
    from dalle_pytorch_tpu.cli.train_vae import main
    main(_common_args(root, w, n_dev, "vae.jsonl") + [
        "--num_layers", str(w.vae_layers),
        "--num_tokens", str(w.num_tokens),
        "--codebook_dim", str(w.codebook_dim),
        "--hidden_dim", str(w.hidden_dim), "--name", "vae"])
    steps, epochs = _losses(os.path.join(root, "vae.jsonl"))
    check(len(epochs) == 1 and all(math.isfinite(x) for x in steps + epochs),
          f"vae losses not finite: {steps} {epochs}")
    path, epoch = ckpt.latest(os.path.join(root, "models"), "vae")
    _, manifest = ckpt.restore_params(path)
    cfg = ckpt.vae_config_from_manifest(manifest)
    check(cfg.image_size == w.image_size and cfg.num_tokens == w.num_tokens,
          f"restored VAE config {cfg}")
    info.update(steps=len(steps), avg_loss=epochs[0])


def phase_train_dalle(root: str, w: Widths, n_dev: int, info: dict) -> None:
    import math

    import numpy as np

    from dalle_pytorch_tpu import checkpoint as ckpt
    from dalle_pytorch_tpu.cli.train_dalle import main
    args = _common_args(root, w, n_dev, "dalle.jsonl") + [
        "--captions_only", os.path.join(root, "only.txt"),
        "--captions", os.path.join(root, "pairs.txt"),
        "--vaename", "vae", "--vae_epoch", "0",
        "--dim", str(w.dim), "--depth", str(w.depth),
        "--heads", str(w.heads), "--dim_head", str(w.dim_head),
        "--text_seq_len", str(w.text_seq_len),
        "--num_text_tokens", str(w.num_text_tokens),
        "--param_dtype", w.param_dtype,
        "--attn_impl", "flash", "--sample_every", "0", "--name", "north"]
    main(args)                                        # fresh: epoch 0
    main(args + ["--load_dalle", "north"])            # restored: epoch 1
    steps, epochs = _losses(os.path.join(root, "dalle.jsonl"))
    check(len(epochs) == 2 and all(math.isfinite(x) for x in steps + epochs),
          f"dalle losses not finite: {steps} {epochs}")
    path, epoch = ckpt.latest(os.path.join(root, "models"), "north_dalle")
    check(epoch == 1, f"resumed run should have written epoch 1, got {epoch}")
    params, manifest = ckpt.restore_params(path)
    cfg = ckpt.dalle_config_from_manifest(manifest)
    check((cfg.dim, cfg.depth, cfg.seq_len) == (w.dim, w.depth, w.seq_len),
          f"restored DALLE config {cfg}")
    dtype = np.asarray(params["text_emb"]["w"]).dtype
    check(str(dtype) == w.param_dtype, f"checkpoint dtype {dtype}")
    info.update(steps=len(steps), avg_loss=epochs, param_dtype=str(dtype),
                attn_impl=cfg.attn_impl)


# ---------------------------------------------------------------------------
# serving phases
# ---------------------------------------------------------------------------

def _post(port: int, body: dict, timeout: float = 900.0) -> dict:
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/generate", data=json.dumps(body).encode())
    try:
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            return json.loads(resp.read())
    except urllib.error.HTTPError as e:
        raise AssertionError(f"POST /generate {body} -> {e.code} "
                             f"{e.read()[:500]!r}") from None


def _post_sse(port: int, body: dict, timeout: float = 900.0):
    """POST a streaming request; -> (event-kind counts, terminal result)."""
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/generate", data=json.dumps(body).encode())
    counts, result, kind = {}, None, None
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        for raw in resp:
            line = raw.decode().rstrip("\n")
            if line.startswith("event: "):
                kind = line[len("event: "):]
                counts[kind] = counts.get(kind, 0) + 1
            elif line.startswith("data: ") and kind == "result":
                result = json.loads(line[len("data: "):])
    return counts, result


def _get(port: int, path: str) -> dict:
    with urllib.request.urlopen(f"http://127.0.0.1:{port}{path}",
                                timeout=60) as resp:
        return json.loads(resp.read())


def _engine_stats(server, stats: dict):
    """One engine-level stats dict per replica: ``/stats`` itself for a
    single engine; for a (thread-mode) replica set, each replica engine's
    own — the set's aggregate carries no per-replica page gauges."""
    if not server._is_set:
        return [stats]
    return [r.engine.stats() for r in server.engine.replicas
            if r.engine is not None]


@contextlib.contextmanager
def served(root: str, w: Widths, extra):
    """The server started exactly the way ``cli.serve`` starts it (paged
    KV, the trained checkpoint) behind the stdlib HTTP facade on an
    ephemeral port; stopped again on exit. -> (server, port)."""
    from dalle_pytorch_tpu.cli import serve as serve_cli
    from dalle_pytorch_tpu.serve.server import make_http_server
    _args, server, _loop = serve_cli.start([
        "--name", "north", "--dalle_epoch", "1",
        "--models_dir", os.path.join(root, "models"),
        "--num_slots", str(w.num_slots),
        "--chunk_steps", str(w.chunk_steps),
        "--kv", "paged", "--page_size", str(w.page_size),
        "--log_every", "0", *extra])
    httpd = make_http_server(server, "127.0.0.1", 0)
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    try:
        yield server, httpd.server_address[1]
    finally:
        httpd.shutdown()
        httpd.server_close()
        server.close()


def phase_serve(root: str, w: Widths, n_dev: int, info: dict,
                paged_attn: str = "gather") -> dict:
    """Drive the server over HTTP, one engine per chip. -> {request
    label: tokens} for cross-run comparison."""
    import jax
    tokens = {}
    with served(root, w, ["--paged_attn", paged_attn, "--prefix_cache",
                          "--preview_every", "2",
                          "--replicas", str(n_dev)]) as (server, port):
        health = _get(port, "/healthz")
        check(health["ok"], f"/healthz not ok: {health}")
        check(health["platform"] == jax.devices()[0].platform
              and health["device_kind"] == jax.devices()[0].device_kind,
              f"/healthz device {health}")

        plain = _post(port, {"caption": "a red square", "seed": 7})
        check(plain["status"] == "ok", f"plain request: {plain}")
        check(len(plain["tokens"]) == w.image_seq_len,
              f"plain request returned {len(plain['tokens'])} tokens")
        tokens["plain"] = plain["tokens"]
        info["image_shape"] = plain.get("image_shape")

        # a prompt of one full KV page plus a partial one: the group's
        # members share the full page physically and fork the partial
        words = [wd for c in COLORS for wd in ("a", c, "square")]
        n_words = min(w.text_seq_len, w.page_size + 4)
        long_caption = " ".join(words[i % len(words)]
                                for i in range(n_words))
        counts, group = _post_sse(port, {
            "caption": long_caption, "seed": 3, "stream": True,
            "n_samples": w.n_samples})
        check(group is not None and group["status"] == "ok",
              f"streamed best-of-{w.n_samples}: {counts} {group}")
        check(len(group["samples"]) == w.n_samples,
              f"group returned {len(group['samples'])} samples")
        check(counts.get("tokens", 0) > 0 and counts.get("preview", 0) > 0,
              f"stream events {counts}")
        tokens["group"] = [s["tokens"] for s in group["samples"]]
        info["stream_events"] = counts

        # two concurrent requests: slots > 1 at once
        both = [None, None]

        def fire(i):
            both[i] = _post(port, {"caption": f"a {COLORS[2 + i]} square",
                                   "seed": 11 + i})
        threads = [threading.Thread(target=fire, args=(i,))
                   for i in range(2)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(900)
        check(all(b is not None and b["status"] == "ok" for b in both),
              f"concurrent requests: {both}")
        tokens["concurrent"] = [b["tokens"] for b in both]

        again = _post(port, {"caption": "a red square", "seed": 7})
        check(again["status"] == "ok" and again["tokens"] == plain["tokens"],
              "the same seed gave different tokens twice")

        if n_dev > 1:
            # one burst wide enough that the router must use every replica
            burst = [None] * (w.num_slots * n_dev)

            def fire_burst(i):
                burst[i] = _post(port, {"caption": "a gray square",
                                        "seed": 100 + i})
            threads = [threading.Thread(target=fire_burst, args=(i,))
                       for i in range(len(burst))]
            for t in threads:
                t.start()
            for t in threads:
                t.join(900)
            check(all(b is not None and b["status"] == "ok" for b in burst),
                  f"burst: {burst}")

        deadline = time.perf_counter() + 60
        while True:          # groups/streams retire asynchronously
            stats = _get(port, "/stats")
            if stats["streams_active"] == 0 \
                    and stats["groups_in_flight"] == 0 \
                    and stats["active_slots"] == 0:
                break
            check(time.perf_counter() < deadline, f"did not drain: {stats}")
            time.sleep(0.2)
        reps = _engine_stats(server, stats)
        check(all(r["decode_compiles"] <= 1 for r in reps)
              and any(r["decode_compiles"] == 1 for r in reps),
              f"decode_compiles {[r['decode_compiles'] for r in reps]}")
        check(all(r["pages_in_use"] == r["prefix_pages_held"] for r in reps),
              "pages_in_use did not return to the prefix-cache hold: "
              f"{[(r['pages_in_use'], r['prefix_pages_held']) for r in reps]}")
        check(stats["preview_frames"] > 0, f"no preview frames: {stats}")
        check(n_dev > 1 or stats["fanout_pages_saved"] > 0,
              f"the group shared no prompt page: {stats}")
        check(stats["paged_attn"] == paged_attn
              if "paged_attn" in stats else True, f"paged_attn: {stats}")
        info.update(decode_compiles=[r["decode_compiles"] for r in reps],
                    completed=stats["completed"],
                    pages_peak=[r["pages_peak"] for r in reps],
                    prefix_hits=stats.get("prefix_hits"),
                    fanout_pages_saved=stats["fanout_pages_saved"],
                    mean_occupancy=stats["mean_occupancy"])
        if n_dev > 1:
            health = _get(port, "/healthz")
            devs = [r.get("device") for r in health["replicas"]]
            check(len(set(devs)) == n_dev and None not in devs,
                  f"replica devices not distinct: {devs}")
            check(all(r["completed"] > 0 for r in reps),
                  f"a replica served nothing: "
                  f"{[r['completed'] for r in reps]}")
            in_use = [d.memory_stats()["bytes_in_use"]
                      for d in jax.devices() if d.memory_stats()]
            check(all(b > 0 for b in in_use),
                  f"a device holds nothing while serving: {in_use}")
            info.update(replica_devices=devs, bytes_in_use=in_use,
                        replica_completed=[r["completed"] for r in reps])
    return tokens


def first_difference(a, b):
    """Index of the first differing token of two streams, or None."""
    for i, (x, y) in enumerate(zip(a, b)):
        if x != y:
            return i
    return None if len(a) == len(b) else min(len(a), len(b))


def compare_serve_tokens(gather: dict, kernel: dict, info: dict) -> None:
    """bf16 parameters: summation order may legitimately move a sampled
    token, so report the first differing position per request instead of
    asserting equality (the f32 equality is the kernels phase's)."""
    flat_g = [gather["plain"], *gather["group"], *gather["concurrent"]]
    flat_k = [kernel["plain"], *kernel["group"], *kernel["concurrent"]]
    info["first_difference_vs_gather"] = [
        first_difference(g, k) for g, k in zip(flat_g, flat_k)]


# ---------------------------------------------------------------------------
# kernels
# ---------------------------------------------------------------------------

def _reldiff(a, b) -> float:
    import jax.numpy as jnp
    a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    return float(jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b)))


def phase_kernels(w: Widths, info: dict) -> None:
    """Every Pallas variant a public flag reaches, compiled by Mosaic at
    the north widths (or interpreted, on the CPU test backend — the
    ``interpreted`` field says which), against its XLA oracle."""
    from dalle_pytorch_tpu.ops import core
    info["interpreted"] = core.pallas_interpret()
    check_train_kernels(w, info)
    check_paged_step_math(w, info)
    check_paged_engines(w, info)


def check_train_kernels(w: Widths, info: dict) -> None:
    """Flash forward with each backward, block-sparse on both schedules:
    outputs and gradients against the f32 XLA oracle, in the trained
    dtype."""
    import jax
    import jax.numpy as jnp

    from dalle_pytorch_tpu.ops.attention import dense_attention_weights
    from dalle_pytorch_tpu.ops.block_sparse import block_sparse_attention
    from dalle_pytorch_tpu.ops.flash_attention import flash_attention
    from dalle_pytorch_tpu.ops.sparse import sparse_attention_ref
    dtype = jnp.dtype(w.param_dtype)
    # block-sparse wants whole 16-token layout blocks (the transformer pads)
    b, h, n, d = 2, w.heads, -(-w.seq_len // 16) * 16, w.dim_head
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(kq, (b, h, n, d), dtype)
    k = jax.random.normal(kk, (b, h, n, d), dtype)
    v = jax.random.normal(kv, (b, h, n, d), dtype)
    # last batch row half-padded: exercises the pad-mask kernel paths
    lens = jnp.full((b, 1), n).at[-1, 0].set(n // 2)
    mask = jnp.arange(n)[None, :] < lens
    scale = d ** -0.5

    def out_and_grads(fn):
        def fwd_bwd(q, k, v):
            out, vjp = jax.vjp(fn, q, k, v)
            return out, vjp(out)          # cotangent = out: a squared loss
        return jax.jit(fwd_bwd)

    def oracle(fn):
        with jax.default_matmul_precision("highest"):
            f32 = (x.astype(jnp.float32) for x in (q, k, v))
            return out_and_grads(fn)(*f32)

    def dense_ref(causal):
        def fn(q, k, v):
            wts = dense_attention_weights(q, k, scale, mask, causal)
            return jnp.einsum("bhij,bhjd->bhid", wts, v)
        return fn

    variants = {}
    ref = oracle(dense_ref(True))
    for bwd in ("xla", "pallas", "pallas_fused"):
        variants[f"flash_bwd_{bwd}"] = (
            lambda q, k, v, bwd=bwd: flash_attention(
                q, k, v, scale=scale, causal=True, mask=mask, bwd_impl=bwd),
            ref)
    for name, causal in (("static", True), ("scanning", False)):
        variants[f"block_sparse_{name}"] = (
            lambda q, k, v, causal=causal: block_sparse_attention(
                q, k, v, scale=scale, causal=causal, mask=mask),
            oracle(lambda q, k, v, causal=causal: sparse_attention_ref(
                q, k, v, scale=scale, causal=causal, mask=mask)))
    diffs = {}
    for name, (fn, (ref_out, ref_grads)) in variants.items():
        out, grads = out_and_grads(fn)(q, k, v)
        diffs[name] = {"fwd": _reldiff(out, ref_out),
                       "grad": max(_reldiff(g, r)
                                   for g, r in zip(grads, ref_grads))}
    info["reldiff"] = {kk_: {m: float(f"{x:.3g}") for m, x in vv.items()}
                       for kk_, vv in diffs.items()}
    bad = {kk_: vv for kk_, vv in diffs.items()
           if not max(vv.values()) < KERNEL_RELDIFF}
    check(not bad, f"kernel parity FAILED (> {KERNEL_RELDIFF}): {bad}")


def check_paged_step_math(w: Widths, info: dict) -> None:
    """Paged attention, the decode step math: the kernel against the
    gather oracle over one random pool at the engine's page size."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from dalle_pytorch_tpu.models import dalle as D
    from dalle_pytorch_tpu.ops import decode as decode_ops
    from dalle_pytorch_tpu.serve import kv_pool as KV
    dtype = jnp.dtype(w.param_dtype)

    def step_math(cfg, pdtype, quantized, sparse_reads=False):
        """(kernel, gather) decode-step outputs over one random pool at
        ragged positions: a slot on its last row, one mid-sequence with
        a padded-off prompt row, one parked dead at pos 0."""
        tcfg = cfg.transformer
        L, ps = cfg.seq_len, w.page_size
        mp = KV.pages_for(L, ps)
        params = D.dalle_init(jax.random.PRNGKey(1), cfg, dtype=pdtype)
        shape = (tcfg.depth, 2 * mp + 1, ps, tcfg.heads * tcfg.dim_head)
        scales = shape[:-1] + (tcfg.heads,)
        key = jax.random.PRNGKey(7)
        if quantized:
            pool = {
                "k": jax.random.randint(jax.random.fold_in(key, 0), shape,
                                        -127, 128, jnp.int8),
                "v": jax.random.randint(jax.random.fold_in(key, 1), shape,
                                        -127, 128, jnp.int8),
                "k_scale": jax.random.uniform(
                    jax.random.fold_in(key, 2), scales, minval=0.01,
                    maxval=0.1),
                "v_scale": jax.random.uniform(
                    jax.random.fold_in(key, 3), scales, minval=0.01,
                    maxval=0.1)}
        else:
            pool = {"k": jax.random.normal(jax.random.fold_in(key, 0),
                                           shape, pdtype),
                    "v": jax.random.normal(jax.random.fold_in(key, 1),
                                           shape, pdtype)}
        bt = np.zeros((3, mp), np.int32)
        bt[0] = np.arange(1, mp + 1)
        bt[1] = np.arange(mp + 1, 2 * mp + 1)
        mid = L // 3
        bt[1, KV.pages_for(mid + 1, ps):] = 0
        bt = jnp.asarray(bt)
        pos = jnp.asarray([L - 1, mid, 0], jnp.int32)
        key_mask = jnp.ones((3, L), bool).at[1, 1].set(False)
        x_tok = jax.random.normal(jax.random.PRNGKey(9), (3, cfg.dim),
                                  pdtype)
        extra = {"sparse_reads": True} if sparse_reads else {}

        @jax.jit
        def both(params, pool, x_tok):
            view = decode_ops.paged_view(pool, bt, L, tcfg.heads)
            gather = decode_ops._decode_step_math(
                params["transformer"], x_tok, pos, view, cfg=tcfg,
                key_mask=key_mask)[0]
            kernel = decode_ops._decode_step_math(
                params["transformer"], x_tok, pos, pool, cfg=tcfg,
                key_mask=key_mask, attn_impl="kernel", block_tables=bt,
                **extra)[0]
            return kernel, gather
        return both(params, pool, x_tok)

    paged = {}
    with jax.default_matmul_precision("highest"):
        # f32 parameters, exact matmuls on both sides: the repo's own
        # tolerance applies (prefix walk, int8 KV, visible-page walk)
        for name, kw in (("f32", {}), ("f32_int8kv", {"quantized": True}),
                         ("f32_visible", {"sparse_reads": True})):
            cfg = _dalle_cfg(w, depth=w.f32_depth,
                             sparse="sparse_reads" in kw)
            kern, gath = step_math(cfg, jnp.float32,
                                   kw.get("quantized", False),
                                   kw.get("sparse_reads", False))
            np.testing.assert_allclose(
                np.asarray(kern), np.asarray(gath), rtol=PAGED_RTOL,
                atol=PAGED_ATOL, err_msg=f"paged kernel vs gather ({name})")
            paged[name] = _reldiff(kern, gath)
    if dtype != jnp.float32:
        # the served dtype: report, bounded like every other bf16 kernel
        for name, quantized in ((w.param_dtype, False),
                                (f"{w.param_dtype}_int8kv", True)):
            kern, gath = step_math(_dalle_cfg(w, depth=w.f32_depth), dtype,
                                   quantized)
            paged[name] = _reldiff(kern, gath)
            check(paged[name] < KERNEL_RELDIFF,
                  f"paged kernel vs gather ({name}): {paged[name]}")
    info["paged_reldiff"] = {kk_: float(f"{x:.3g}")
                             for kk_, x in paged.items()}


def check_paged_engines(w: Widths, info: dict) -> None:
    """... and inside the fused decode scan: f32 engines, kernel vs
    gather, byte-identical tokens over a transfer-clean steady state —
    the prefix walk, the int8-KV pool, and a sparse model's visible-page
    walk against the plain gather oracle."""
    import jax

    from dalle_pytorch_tpu.analysis import guards
    from dalle_pytorch_tpu.models import dalle as D
    from dalle_pytorch_tpu.models import vae as V
    from dalle_pytorch_tpu.serve import Request, RequestQueue
    from dalle_pytorch_tpu.serve.engine import Engine
    vae_params = V.vae_init(jax.random.PRNGKey(2), _dalle_cfg(w).vae)
    equal = {}
    with jax.default_matmul_precision("highest"):
        for name, sparse, quantized in (("prefix", False, False),
                                        ("int8kv", False, True),
                                        ("visible", True, False)):
            cfg = _dalle_cfg(w, depth=w.f32_depth, sparse=sparse)
            params = D.dalle_init(jax.random.PRNGKey(3), cfg, vae_params)
            streams = {}
            for impl in ("gather", "kernel"):
                queue = RequestQueue(max_depth=8)
                engine = Engine(
                    params, cfg, queue, num_slots=2,
                    chunk_steps=w.chunk_steps, kv="paged",
                    page_size=w.page_size, paged_attn=impl,
                    quantize_cache=quantized,
                    sparse_reads=sparse and impl == "kernel")
                warm = queue.submit(Request(
                    codes=(1, 2, 3), seed=0,
                    image_seq_len_override=w.chunk_steps))
                engine.run_until_idle()
                check(warm.result(timeout=60).status == "ok",
                      f"{name}/{impl}: warmup request")
                with guards.no_transfers():
                    handles = [queue.submit(Request(
                        codes=(3, 7, 9 + i), seed=11 + i,
                        image_seq_len_override=w.f32_tokens))
                        for i in range(2)]
                    engine.run_until_idle()
                results = [hd.result(timeout=60) for hd in handles]
                check(all(r.status == "ok" for r in results),
                      f"{name}/{impl}: {[r.reason for r in results]}")
                check(engine.decode_traces == 1, f"{name}/{impl}: "
                      f"decode_traces {engine.decode_traces}")
                streams[impl] = [[int(t) for t in r.tokens]
                                 for r in results]
            diff = [first_difference(g, kn) for g, kn in
                    zip(streams["gather"], streams["kernel"])]
            check(diff == [None, None], f"f32 {name} engines: kernel "
                  f"tokens differ from gather at {diff}")
            equal[name] = True
    info["f32_engine_tokens_equal"] = equal


# ---------------------------------------------------------------------------
# the sync rule
# ---------------------------------------------------------------------------

def _dalle_cfg(w: Widths, depth: int = 0, sparse: bool = False):
    """The model the CLIs train from these widths (flash attention),
    optionally at a cut ``depth`` / with alternating sparse layers."""
    from dalle_pytorch_tpu.models import dalle as D
    from dalle_pytorch_tpu.models import vae as V
    depth = depth or w.depth
    vcfg = V.VAEConfig(image_size=w.image_size, num_tokens=w.num_tokens,
                       codebook_dim=w.codebook_dim,
                       num_layers=w.vae_layers, hidden_dim=w.hidden_dim)
    return D.DALLEConfig(
        dim=w.dim, depth=depth, vae=vcfg,
        num_text_tokens=w.num_text_tokens, text_seq_len=w.text_seq_len,
        heads=w.heads, dim_head=w.dim_head, attn_impl="flash",
        sparse_attn=(True, False) * (depth // 2) if sparse else False)


def _train_setup(cfg, batch: int, mesh, dtype, param_specs_fn=None,
                 batch_axis: str = "dp"):
    import jax
    import optax

    from dalle_pytorch_tpu.models import dalle as D
    from dalle_pytorch_tpu.parallel import shard_batch
    from dalle_pytorch_tpu.parallel.train import (dalle_loss_fn,
                                                  make_train_step,
                                                  setup_sharded)
    key = jax.random.PRNGKey(0)
    params = D.dalle_init(key, cfg, dtype=dtype)
    opt = optax.adam(1e-4)
    specs = param_specs_fn(params) if param_specs_fn else None
    params, opt_state = setup_sharded(params, opt, mesh, specs)
    step = make_train_step(dalle_loss_fn(cfg), opt)
    data = shard_batch(mesh, {
        "text": jax.random.randint(jax.random.fold_in(key, 1),
                                   (batch, cfg.text_seq_len), 0,
                                   cfg.num_text_tokens),
        "image": jax.random.randint(jax.random.fold_in(key, 2),
                                    (batch, cfg.image_seq_len), 0,
                                    cfg.num_image_tokens)}, axis=batch_axis)
    return step, params, opt_state, data, key


def phase_sync(w: Widths, n_dev: int, info: dict) -> None:
    """The timing rule (PERF.md section 2), settled on the device it runs
    on: the same N chained train steps timed once ending in
    ``jax.block_until_ready`` and once in a host fetch of the last loss
    must agree. A check, printed as such — not a metric."""
    import math

    import jax
    import jax.numpy as jnp

    from dalle_pytorch_tpu.parallel import make_mesh
    cfg = _dalle_cfg(w)
    batch = w.batch * n_dev
    step, params, opt_state, data, key = _train_setup(
        cfg, batch, make_mesh({"dp": n_dev}), jnp.dtype(w.param_dtype))

    def run(n, first):
        nonlocal params, opt_state
        for i in range(n):
            params, opt_state, loss = step(params, opt_state, data,
                                           jax.random.fold_in(key,
                                                              first + i))
        return loss

    float(run(2, 0))                                  # compile + settle
    t0 = time.perf_counter()
    jax.block_until_ready(run(w.sync_steps, 100))
    t_block = time.perf_counter() - t0
    t0 = time.perf_counter()
    loss = float(run(w.sync_steps, 200))
    t_fetch = time.perf_counter() - t0
    info.update(check_block_until_ready_s=round(t_block, 4),
                check_host_fetch_s=round(t_fetch, 4), loss=loss)
    check(math.isfinite(loss), f"loss {loss}")
    check(abs(t_block - t_fetch) <= w.sync_tolerance * max(t_block, t_fetch),
          f"block_until_ready ({t_block:.4f}s) and host fetch "
          f"({t_fetch:.4f}s) disagree on the same {w.sync_steps} steps")


# ---------------------------------------------------------------------------
# more than one chip
# ---------------------------------------------------------------------------

def phase_multichip(root: str, w: Widths, n_dev: int, info: dict) -> None:
    """Every device holds its share under the trainer's default dp mesh;
    one train step of a tp x fsdp mesh; one ``--mesh_devices`` engine
    answers. (``--replicas n_dev`` is the serve phases' own shape.)"""
    import jax
    import jax.numpy as jnp

    from dalle_pytorch_tpu.parallel import make_mesh
    from dalle_pytorch_tpu.parallel.train import dalle_param_specs
    devices = jax.devices()
    info["device_order"] = [
        {"id": d.id, "coords": getattr(d, "coords", None),
         "core": getattr(d, "core_on_chip", None)} for d in devices]
    cfg = _dalle_cfg(w)
    dtype = jnp.dtype(w.param_dtype)

    # the trainer's default mesh (cli/common.py setup_run): dp = all
    mesh = make_mesh({"dp": n_dev})
    step, params, opt_state, data, key = _train_setup(
        cfg, w.batch * n_dev, mesh, dtype)
    shards = data["text"].addressable_shards
    check(sorted(s.device.id for s in shards)
          == sorted(d.id for d in devices)
          and all(s.data.shape[0] == w.batch for s in shards),
          f"dp batch shards {[(s.device.id, s.data.shape) for s in shards]}")
    leaf = params["text_emb"]["w"]
    check(len(leaf.addressable_shards) == n_dev
          and all(s.data.shape == leaf.shape
                  for s in leaf.addressable_shards),
          "dp params are not one full copy per device")
    params, opt_state, loss = step(params, opt_state, data, key)
    check(bool(jnp.isfinite(loss)), f"dp loss {loss}")
    info["dp_loss"] = float(loss)
    del params, opt_state, data

    tp = 2 if n_dev % 2 == 0 else 1
    axes = {"tp": tp, "fsdp": n_dev // tp}
    mesh = make_mesh(axes)
    step, params, opt_state, data, key = _train_setup(
        cfg, w.batch * n_dev, mesh, dtype,
        param_specs_fn=lambda p: dalle_param_specs(
            p, tp="tp", fsdp="fsdp", mesh=mesh), batch_axis="fsdp")
    sharded = [lf for lf in jax.tree.leaves(params)
               if lf.addressable_shards[0].data.size < lf.size]
    check(sharded, "tp x fsdp placed no parameter sharded")
    params, opt_state, loss = step(params, opt_state, data, key)
    check(bool(jnp.isfinite(loss)), f"tp x fsdp loss {loss}")
    info.update(tp_fsdp_mesh=axes, tp_fsdp_loss=float(loss),
                tp_fsdp_sharded_leaves=len(sharded))
    del params, opt_state, data
    peaks = [d.memory_stats()["peak_bytes_in_use"]
             for d in devices if d.memory_stats()]
    check(all(p > 0 for p in peaks), f"a device never held bytes: {peaks}")

    # one engine over all chips: params + KV sharded over the mesh slice
    # (the mesh engine reads through the gather path — the kernel is a
    # typed refusal there, serve/mesh_engine.py)
    with served(root, w, ["--mesh_devices", str(n_dev)]) as (_, port):
        out = _post(port, {"caption": "a red square", "seed": 7})
        check(out["status"] == "ok", f"mesh engine request: {out}")
        health, stats = _get(port, "/healthz"), _get(port, "/stats")
        info["mesh_engine"] = {
            "devices_per_replica": health["devices_per_replica"],
            "mesh_shape": health["mesh_shape"],
            "decode_compiles": stats["decode_compiles"],
            "tokens": len(out["tokens"])}


# ---------------------------------------------------------------------------
# the run
# ---------------------------------------------------------------------------

def run_phases(w: Widths, report: Report, root: str) -> None:
    """Every phase, in order, each reported; a failed phase fails the
    phases that need its output and lets the others run."""
    import jax

    from dalle_pytorch_tpu import native
    n_dev = len(jax.devices())

    def data(info):
        make_dataset(root, w, n_images=w.batch * n_dev * w.steps)
        # the image decode path the trainers will take: the native loader
        # builds lazily with the host toolchain, else PIL
        info["image_decode"] = "native" if native.available() else "PIL"

    def serve_kernel(info):
        kernel = phase_serve(root, w, n_dev, info, "kernel")
        compare_serve_tokens(gather, kernel, info)

    report.run("data", data)
    report.run("train_vae", lambda info: phase_train_vae(root, w, n_dev, info),
               needs=["data"])
    report.run("train_dalle",
               lambda info: phase_train_dalle(root, w, n_dev, info),
               needs=["train_vae"])
    gather = report.run(
        "serve", lambda info: phase_serve(root, w, n_dev, info, "gather"),
        needs=["train_dalle"])
    report.run("serve_kernel", serve_kernel, needs=["serve"])
    report.run("kernels", lambda info: phase_kernels(w, info))
    report.run("sync", lambda info: phase_sync(w, n_dev, info))
    if n_dev > 1:
        report.run("multichip",
                   lambda info: phase_multichip(root, w, n_dev, info),
                   needs=["train_dalle"])


def main() -> int:
    import jax

    from dalle_pytorch_tpu.utils.device import (chip_peaks, describe_device,
                                                enable_compile_cache)
    device = describe_device()
    if device["platform"] != "tpu":
        print(f"chip_smoke: jax found no TPU (got {device}); this script "
              f"proves the main path on the chip and refuses anything else",
              file=sys.stderr)
        return 2

    import importlib.metadata as md

    import jaxlib

    from dalle_pytorch_tpu.ops import core
    cache_dir = enable_compile_cache()
    peaks = chip_peaks(device["kind"])        # unknown device: an error
    try:
        libtpu = md.version("libtpu")
    except md.PackageNotFoundError:
        libtpu = "unknown"
    print(json.dumps({"device": device, "jax": jax.__version__,
                      "jaxlib": jaxlib.__version__, "libtpu": libtpu,
                      "compile_cache": cache_dir,
                      "peak_bf16_flops": peaks["bf16_flops"],
                      "peaks_source": peaks["source"]}), flush=True)
    if core.pallas_interpret():
        print("chip_smoke: kernels would run interpreted on a TPU backend",
              file=sys.stderr)
        return 2

    def overdue():
        print(json.dumps({"ok": False, "device": device,
                          "error": f"no result within {DEADLINE_S:.0f} s"}),
              flush=True)
        os._exit(4)
    watchdog = threading.Timer(DEADLINE_S - (time.perf_counter() - T0),
                               overdue)
    watchdog.daemon = True
    watchdog.start()

    report = Report()
    root = tempfile.mkdtemp(prefix="chip_smoke_")
    try:
        run_phases(NORTH, report, root)
    finally:
        shutil.rmtree(root, ignore_errors=True)
    total = {"wall_s": round(time.perf_counter() - T0, 1),
             "compile_s": round(sum(p.get("compile_s", 0)
                                    for p in report.phases), 1),
             "failed": report.failed}
    print(json.dumps({"total": total}), flush=True)
    out_dir = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "chiprun_out")
    try:
        os.makedirs(out_dir, exist_ok=True)
        name = f"chip_smoke_{device['count']}chip.jsonl"
        with open(os.path.join(out_dir, name), "a") as f:     # one line a run
            f.write(json.dumps({"device": device, "phases": report.phases,
                                "total": total}) + "\n")
    except OSError:
        pass                      # the report is a convenience copy
    if report.failed:
        print(json.dumps({"ok": False, "failed": report.failed,
                          "device": device}), flush=True)
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    sys.stderr.flush()
    # every thread this process started is a daemon; leave without waiting
    # on interpreter teardown of a live accelerator runtime
    os._exit(code)

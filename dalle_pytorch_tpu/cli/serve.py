"""Serving CLI — the continuous-batching engine behind an HTTP front-end.

Where ``gen_dalle`` pays compile + prefill + full decode per invocation,
this keeps ONE warm engine: the slot-batched decode program compiles once
at startup, then requests stream through the slot pool (docs/SERVING.md).
Checkpoint loading follows gen_dalle's contract exactly (DALLE checkpoint
points at its VAE via meta.vae_checkpoint; vocab JSON from train_dalle;
optional CLIP for scoring; optional EMA weights; optional int8 weight/KV
quantization).

Run: python -m dalle_pytorch_tpu.cli.serve --name test --dalle_epoch 99 \
        --port 8000
Then: curl -s localhost:8000/generate -d '{"caption": "a flower"}'
      curl -s localhost:8000/stats
"""

from __future__ import annotations

import argparse
import os

import jax

from dalle_pytorch_tpu import checkpoint as ckpt
from dalle_pytorch_tpu.cli.common import ema_as, say
from dalle_pytorch_tpu.data import Vocabulary, read_captions_only
from dalle_pytorch_tpu.models import dalle as D
from dalle_pytorch_tpu.utils import MetricsLogger


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        description="serve text->image generation (continuous batching)")
    p.add_argument("--name", type=str, default="test",
                   help="DALLE experiment name (as given to train_dalle)")
    p.add_argument("--dalle_epoch", type=int, default=0)
    p.add_argument("--models_dir", type=str, default="./models")
    p.add_argument("--vocab", type=str, default="",
                   help="vocab JSON (default: {models_dir}/{name}-vocab.json)")
    p.add_argument("--captions_only", type=str, default="",
                   help="rebuild vocab from this corpus instead")
    p.add_argument("--clip_name", type=str, default="",
                   help="CLIP checkpoint name for result scoring")
    p.add_argument("--clip_epoch", type=int, default=0)
    p.add_argument("--use_ema", action="store_true",
                   help="serve the checkpoint's EMA weights")
    p.add_argument("--quantize", choices=("none", "int8", "int8_kv"),
                   default="none",
                   help="int8 transformer/head weights; int8_kv also "
                        "stores the slot-pool KV cache int8 (gen_dalle's "
                        "flags, engine-wide here)")
    p.add_argument("--num_slots", type=int, default=4,
                   help="decode slot-pool size — the fixed batch the one "
                        "compiled decode program advances every step")
    p.add_argument("--chunk_steps", type=int, default=8,
                   help="decode steps fused per device program (K): the "
                        "host harvests emitted tokens once per K steps "
                        "instead of once per step, and a finishing "
                        "request waits up to K-1 extra steps for its "
                        "result — pick K against your latency deadline "
                        "(docs/SERVING.md 'Choosing K')")
    p.add_argument("--prefill_buckets", type=str, default="",
                   help="comma list of prompt-length buckets admission "
                        "pads up to (must end at text_seq_len); default "
                        "= powers of two up to text_seq_len. One prefill "
                        "compile per bucket, ever")
    p.add_argument("--kv", choices=("dense", "paged"), default="dense",
                   help="KV-cache layout: 'dense' reserves num_slots x "
                        "seq_len rows up front; 'paged' shares a page "
                        "pool through per-slot block tables so HBM "
                        "residency tracks actual positions — more "
                        "concurrency per byte, with typed page "
                        "backpressure (docs/SERVING.md 'Paged KV')")
    p.add_argument("--page_size", type=int, default=0,
                   help="rows per KV page (paged mode; 0 = default 16). "
                        "Smaller pages waste fewer rows per request but "
                        "widen the block tables")
    p.add_argument("--paged_attn", choices=("gather", "kernel"),
                   default="gather",
                   help="paged K/V read implementation: 'gather' "
                        "materializes a dense view through the block "
                        "tables every step (the parity oracle); "
                        "'kernel' runs the Pallas ragged paged-"
                        "attention kernel, which walks the block "
                        "tables in place and moves only each "
                        "request's LIVE pages HBM->VMEM — the "
                        "per-token read-traffic lever (docs/SERVING.md "
                        "'Paged attention kernel'). Requires --kv "
                        "paged and a page_size that is a multiple of "
                        "8 (the kernel's VMEM tile)")
    p.add_argument("--sparse_reads", action="store_true",
                   help="sparsity-aware decode reads (requires --kv "
                        "paged and a model with sparse layers): sparse "
                        "layers read only their statically visible KV "
                        "pages — the trained block-local window plus "
                        "the global text anchor — instead of the whole "
                        "cached prefix. Tokens stay byte-identical "
                        "(skipped pages carry exactly-zero attention "
                        "weight); per-token KV read traffic drops by "
                        "the visibility ratio (docs/SERVING.md 'Sparse "
                        "decode reads')")
    p.add_argument("--speculative", type=int, default=0,
                   help="speculative decode: draft-and-verify with k "
                        "tokens per round (0 = off). A shallow draft "
                        "head — the first --draft_layers transformer "
                        "layers plus the same logit head, no extra "
                        "weights — proposes k-1 tokens, ONE k-wide "
                        "full-model pass verifies all of them, and the "
                        "longest matching prefix is accepted. "
                        "Deterministic per-position sampling makes the "
                        "emitted stream byte-identical to eager decode "
                        "at every acceptance rate; only latency "
                        "changes (docs/SERVING.md 'Speculative "
                        "decode'). Composes with --kv dense/paged and "
                        "--paged_attn, not with --sparse_reads")
    p.add_argument("--draft_layers", type=int, default=0,
                   help="draft depth d for --speculative (0 = depth/2): "
                        "more layers -> higher acceptance, costlier "
                        "drafts; the sweet spot is where d/depth * k "
                        "extra draft FLOPs still undercut the "
                        "sequential full-depth steps the accepted "
                        "tokens skip")
    p.add_argument("--prefix_cache", action="store_true",
                   help="cross-request prefix cache (requires --kv "
                        "paged): prompt KV pages become refcounted, "
                        "copy-on-write, content-addressed — a repeated "
                        "prompt (retry storm, shared style prefix, "
                        "N samples per prompt) admits WARM: its prompt "
                        "pages map into the new request's block table "
                        "physically (zero prefill FLOPs, zero new pages "
                        "for the shared span) and only the generated "
                        "span allocates. Sharing is read-only by "
                        "construction; under page pressure the LRU end "
                        "of the index is dropped before any live "
                        "request is evicted (docs/SERVING.md 'Prefix "
                        "cache & per-request CFG')")
    p.add_argument("--cfg_scale", type=float, default=0.0,
                   help="default classifier-free guidance scale for "
                        "requests that don't carry their own "
                        "(POST /generate {\"cfg_scale\": ...} "
                        "overrides per request; 0 = unguided). A "
                        "guided request runs a cond/uncond slot pair "
                        "whose image tokens sample from l_u + "
                        "scale*(l_c - l_u) — gen_dalle's --guidance, "
                        "per request. With --prefix_cache the pair "
                        "shares its prompt pages physically (the null "
                        "caption is ONE cache entry for all guided "
                        "traffic), so guidance costs < 2x pages. "
                        "Train with --caption_drop so the model has "
                        "seen null captions")
    p.add_argument("--num_pages", type=int, default=0,
                   help="physical pages in the pool incl. the reserved "
                        "trash page (paged mode; 0 = fully provisioned: "
                        "num_slots x ceil(seq_len/page_size) + 1, i.e. "
                        "no overcommit). Smaller = overcommit: admission "
                        "defers on page pressure and mid-decode "
                        "exhaustion evicts the lowest-priority request "
                        "back to the queue")
    p.add_argument("--replicas", type=int, default=1,
                   help="engine replicas behind the one queue (each its "
                        "own thread and, with multiple devices, its own "
                        "chip). A replica that crashes or hangs is "
                        "fenced and its in-flight requests replay on a "
                        "survivor with bit-identical tokens — zero "
                        "requests lost (docs/SERVING.md 'Replica set & "
                        "failover')")
    p.add_argument("--replica_roles", type=str, default="",
                   help="comma list of per-replica roles, one per "
                        "--replicas entry (prefill|decode|both; e.g. "
                        "'prefill,decode'): disaggregated serving. A "
                        "'prefill' replica admits and prefills new "
                        "requests, then LIVE-MIGRATES each warm request "
                        "— its mapped KV pages, block table, and decode "
                        "cursor — to a 'decode' replica, which carries "
                        "it to completion byte-identical; decode "
                        "replicas are routed new work only when no "
                        "prefill-capable replica has capacity. Roles "
                        "are a routing preference, not a capability "
                        "wall: zero-loss always outranks the role "
                        "split. Requires --kv paged (docs/SERVING.md "
                        "'Live migration & disaggregated roles')")
    p.add_argument("--mesh_devices", type=int, default=1,
                   help="devices per engine: >1 serves ONE logical "
                        "engine pjit-sharded over an ICI mesh slice of "
                        "that many chips — params shard by depth, the "
                        "KV pool by heads, tokens stay byte-identical "
                        "to the single-chip engine — so a model whose "
                        "params + KV pool exceed one device's HBM "
                        "still serves. Composes with --replicas: each "
                        "replica becomes a mesh SLICE (replica i gets "
                        "devices [i*m, (i+1)*m)), and failover/replay "
                        "carry over unchanged (docs/SERVING.md "
                        "'Mesh-sharded engine')")
    p.add_argument("--worker_ckpt", type=str, default=None,
                   help="socket transport: attach spec carries this "
                        "CHECKPOINT PATH instead of pickled params — "
                        "each worker loads + validates it locally "
                        "(checkpoint.validate; 'latest:<models_dir>:"
                        "<name>' resolves the newest valid epoch), so "
                        "weights never cross the wire and a remote "
                        "host serves from its own checkpoint store. "
                        "--use_ema/--quantize compose: each worker "
                        "re-applies them after its local load, so "
                        "every replica serves identical weights. "
                        "An invalid/missing checkpoint (or EMA asked "
                        "of an EMA-less checkpoint) is a typed "
                        "worker death (exit 5) on /healthz, not a "
                        "crash to diff")
    p.add_argument("--isolation", choices=("thread", "process"),
                   default="thread",
                   help="replica isolation (replicas > 1): 'thread' = "
                        "replicas share this process (cheapest); "
                        "'process' = each replica's engine in a "
                        "spawned child process with its own jax "
                        "client, so a segfault, host OOM kill, or "
                        "kill -9 of one replica costs latency on the "
                        "requests it held — replayed token-exact on a "
                        "survivor — never the server (docs/SERVING.md "
                        "'Process isolation')")
    p.add_argument("--transport", choices=("pipe", "socket"),
                   default="pipe",
                   help="process-isolation frame transport: 'pipe' = "
                        "duplex pipe to locally spawned children; "
                        "'socket' = workers DIAL BACK to this server's "
                        "listener with an authenticated HELLO, which "
                        "is what makes host-per-engine isolation and "
                        "remote workers possible — a connection reset, "
                        "torn frame, stalled link, or duplicated/"
                        "reordered delivery fences the replica and its "
                        "work replays token-exact on a survivor "
                        "(docs/SERVING.md 'Host isolation & socket "
                        "transport')")
    p.add_argument("--worker_endpoint", type=str,
                   default="127.0.0.1:0",
                   help="socket transport: HOST:PORT the worker "
                        "listener binds (port 0 = ephemeral; bind "
                        ":PORT or 0.0.0.0:PORT so workers on other "
                        "hosts can reach it). The bound endpoint and "
                        "attach token are printed at startup")
    p.add_argument("--worker_cmd", type=str, default=None,
                   help="socket transport: launcher command run once "
                        "per replica with {endpoint}, {index}, and "
                        "{token} placeholders (e.g. 'ssh tpu-b env "
                        "DALLE_WORKER_TOKEN={token} python -m "
                        "dalle_pytorch_tpu.serve.worker --connect "
                        "{endpoint} --index {index}' — a plain env "
                        "var does not cross ssh, so the remote form "
                        "inlines it; local launchers can rely on the "
                        "DALLE_WORKER_TOKEN env var instead and skip "
                        "{token}). Pass an EMPTY string to launch "
                        "nothing and attach hand-started workers. "
                        "Default: spawn local children that dial back")
    p.add_argument("--attach_token", type=str, default=None,
                   help="socket transport: the shared HELLO token "
                        "(default: generated and printed; hand-started "
                        "workers export it as DALLE_WORKER_TOKEN)")
    p.add_argument("--child_rss_limit_mb", type=int, default=0,
                   help="process isolation: a child worker whose RSS "
                        "crosses this dies with exit 137 (the "
                        "container OOM-kill convention) and is fenced "
                        "+ replayed like any other child death; 0 = "
                        "no limit")
    p.add_argument("--heartbeat_s", type=float, default=5.0,
                   help="replica hang detection: a replica whose "
                        "serving loop misses heartbeats for this long "
                        "is fenced and failed over (replicas > 1 only). "
                        "Set it well above your worst-case fused-chunk "
                        "time (chunks are O(10ms); too tight and a "
                        "slow harvest reads as a hang -> needless "
                        "failover churn)")
    p.add_argument("--queue_depth", type=int, default=64,
                   help="bounded admission queue; submissions past this "
                        "are rejected with a structured 429")
    p.add_argument("--preview_every", type=int, default=0,
                   help="progressive previews for streamed requests "
                        "(POST /generate {\"stream\": true}): every N "
                        "harvested chunks the postprocess thread decodes "
                        "the image-token PREFIX through the VAE and "
                        "pushes a 'preview' SSE frame — the image "
                        "sharpens as tokens land, and the final frame is "
                        "byte-identical to the non-streamed result. 0 = "
                        "token streaming only, no intermediate frames "
                        "(docs/SERVING.md 'Streaming, fan-out & variable "
                        "resolution'). Thread-isolation replicas only")
    p.add_argument("--stream_max_events", type=int, default=256,
                   help="per-stream event ring size: a consumer that "
                        "falls this far behind sheds its OLDEST pending "
                        "tokens/preview events (typed 'overflow' event "
                        "names the gap; the terminal result is always "
                        "complete) — the engine never blocks on a slow "
                        "SSE reader")
    p.add_argument("--admin_token", type=str, default="",
                   help="bearer token for the POST /admin/scale "
                        "operator endpoint (add/remove/drain/undrain "
                        "replicas, rolling weight upgrade, status). "
                        "Default: generated and printed at startup")
    p.add_argument("--max_replicas", type=int, default=0,
                   help="hard cap on fleet width for runtime scale-out "
                        "(POST /admin/scale {\"op\": \"add\"} and the "
                        "autoscaler): every replica allocates its own "
                        "KV page pool, so width is an HBM page budget "
                        "— growing past the cap is a typed 409, never "
                        "a silent clamp. 0 = no runtime growth beyond "
                        "--replicas")
    p.add_argument("--min_replicas", type=int, default=0,
                   help="autoscaler floor (0 = --replicas): scale-in "
                        "never retires below this many replicas")
    p.add_argument("--autoscale", action="store_true",
                   help="run the load-driven autoscaler "
                        "(serve/autoscale.py): watch slot occupancy, "
                        "queue depth, and page pressure, and add/"
                        "remove replicas through the same scale API "
                        "the admin endpoint uses — hysteresis + "
                        "cooldown, capped by --min_replicas/"
                        "--max_replicas, every decision a structured "
                        "autoscale_decision event. Requires "
                        "--max_replicas > --replicas (headroom to "
                        "grow into)")
    p.add_argument("--autoscale_high", type=float, default=0.85,
                   help="autoscaler: mean slot occupancy above this "
                        "(sustained) triggers scale-out")
    p.add_argument("--autoscale_low", type=float, default=0.25,
                   help="autoscaler: occupancy below this with an "
                        "empty queue (sustained) triggers scale-in")
    p.add_argument("--autoscale_cooldown_s", type=float, default=10.0,
                   help="autoscaler: silence after any scale action "
                        "(a fresh replica needs time to compile and "
                        "drain the backlog before the signals are "
                        "believable again)")
    p.add_argument("--autoscale_interval_s", type=float, default=1.0,
                   help="autoscaler: seconds between policy ticks")
    p.add_argument("--gateway", action="store_true",
                   help="run the multi-cell gateway tier: --cells "
                        "independent InferenceServers ('cells', each "
                        "with its own --replicas/--kv/... as configured "
                        "here) behind one HTTP surface with prefix-"
                        "affinity routing, per-tenant quotas, weighted-"
                        "fair queueing, and hedged sends "
                        "(docs/SERVING.md 'Gateway tier')")
    p.add_argument("--cells", type=int, default=2,
                   help="gateway mode: number of cells (each one full "
                        "InferenceServer / ReplicaSet)")
    p.add_argument("--tenants", type=str, default="",
                   help="gateway mode: path to the tenant JSON (list of "
                        "{name, key, weight, rps, image_tokens_per_s, "
                        "max_pages, tier}); hot-reloadable via the "
                        "authenticated POST /admin/tenants. Empty = "
                        "anonymous single-tenant gateway (no auth, no "
                        "quotas)")
    p.add_argument("--host", type=str, default="127.0.0.1")
    p.add_argument("--port", type=int, default=8000)
    p.add_argument("--metrics", type=str, default="",
                   help="JSONL metrics file (engine stats + structured "
                        "serve events)")
    p.add_argument("--profile_dir", type=str, default="",
                   help="default sink for POST /admin/profile: the "
                        "authenticated endpoint wraps the next K fused "
                        "decode chunks in a jax.profiler trace capture "
                        "written here (view in TensorBoard/Perfetto) — "
                        "kernel tuning on a real chip without stopping "
                        "the server. A capture already in flight is a "
                        "typed 409 (docs/OBSERVABILITY.md 'Profiler "
                        "runbook')")
    p.add_argument("--log_every", type=int, default=50,
                   help="emit an engine-stats record every N decode steps")
    p.add_argument("--init_deadline_s", type=float, default=300.0,
                   help="bound backend bring-up per attempt (0 = "
                        "unbounded), with backoff+jitter retries")
    p.add_argument("--init_retries", type=int, default=3)
    return p


def load_vocab(args):
    if args.captions_only:
        return Vocabulary.from_captions(read_captions_only(
            args.captions_only))
    path = args.vocab or os.path.join(args.models_dir,
                                      f"{args.name}-vocab.json")
    return Vocabulary.load(path)


def start(argv=None):
    """Everything ``main`` does short of the blocking HTTP loop: restore
    the checkpoints, build and start the server (or the gateway over its
    cells). -> ``(args, front, serve_loop)`` where ``serve_loop(front,
    host, port)`` is the blocking loop for that front door. Split out so
    a driver that must stop the server again (chip_smoke.py) starts it
    exactly the way the CLI does."""
    from dalle_pytorch_tpu.utils.device import enable_compile_cache
    args = build_parser().parse_args(argv)
    enable_compile_cache()

    dalle_path = ckpt.ckpt_path(args.models_dir, f"{args.name}_dalle",
                                args.dalle_epoch)
    params, manifest = ckpt.restore_params(dalle_path)
    cfg = ckpt.dalle_config_from_manifest(manifest)
    vae_path = manifest["meta"].get("vae_checkpoint")
    if not vae_path or not os.path.isdir(vae_path):
        raise FileNotFoundError(
            f"DALLE checkpoint {dalle_path} does not point at a VAE "
            "checkpoint (meta.vae_checkpoint)")
    vae_params, _ = ckpt.restore_params(vae_path)
    if args.use_ema:
        ema = ckpt.restore_ema(dalle_path)
        if ema is None:
            raise FileNotFoundError(
                f"{dalle_path} has no EMA weights — train with "
                "--ema_decay to serve an EMA")
        params = ema_as(ema, params)
        say("serving EMA weights")
    params = jax.device_put(params)
    vae_params = jax.device_put(vae_params)
    if args.quantize in ("int8", "int8_kv"):
        params = D.quantize_for_decode(params)

    clip_params, clip_cfg = None, None
    if args.clip_name:
        from dalle_pytorch_tpu.models.clip import CLIPConfig
        clip_path = ckpt.ckpt_path(args.models_dir, args.clip_name,
                                   args.clip_epoch)
        clip_params, clip_manifest = ckpt.restore_params(clip_path)
        clip_params = jax.device_put(clip_params)
        clip_cfg = CLIPConfig(**clip_manifest["config"])

    vocab = load_vocab(args)
    metrics = MetricsLogger(args.metrics or None) if args.metrics else None

    from dalle_pytorch_tpu.serve.server import InferenceServer, serve_http
    buckets = None
    if args.prefill_buckets:
        try:
            buckets = [int(b) for b in args.prefill_buckets.split(",")]
        except ValueError:
            raise SystemExit(f"--prefill_buckets must be comma-separated "
                             f"ints, got {args.prefill_buckets!r}")
    autoscale = None
    if args.autoscale:
        from dalle_pytorch_tpu.serve.autoscale import AutoscalePolicy
        if args.max_replicas <= args.replicas:
            raise SystemExit(
                "--autoscale needs --max_replicas > --replicas "
                "(headroom for the scaler to grow into)")
        autoscale = AutoscalePolicy(
            min_replicas=args.min_replicas or args.replicas,
            max_replicas=args.max_replicas,
            high_occupancy=args.autoscale_high,
            low_occupancy=args.autoscale_low,
            cooldown_s=args.autoscale_cooldown_s,
            interval_s=args.autoscale_interval_s)

    def load_weights(path: str):
        # the admin endpoint's rolling-upgrade loader: resolve +
        # validate + restore exactly the way a checkpoint-path worker
        # does (serve/worker.py), re-applying this server's startup
        # transforms — so the upgraded fleet serves weights
        # byte-identical to a fresh `serve_dalle` on the new checkpoint
        from dalle_pytorch_tpu.serve.worker import load_ckpt_params
        return jax.device_put(load_ckpt_params({
            "ckpt_path": path, "ckpt_use_ema": args.use_ema,
            "ckpt_quantize": args.quantize}))

    if args.worker_ckpt and (args.use_ema or args.quantize != "none"):
        # the attach spec carries the SAME transforms the parent just
        # applied to its local copy: each worker re-applies them after
        # its local load (serve/worker.py load_ckpt_params), so every
        # replica serves identical weights — the PR-11 rejection of
        # this combination is gone
        say(f"worker_ckpt: workers apply use_ema={args.use_ema} "
            f"quantize={args.quantize} after their local load")
    def build_server():
        return InferenceServer(
            params, vae_params, cfg, num_slots=args.num_slots,
        queue_depth=args.queue_depth, chunk_steps=args.chunk_steps,
        prefill_buckets=buckets,
        quantize_cache=args.quantize == "int8_kv",
        kv=args.kv, page_size=args.page_size, num_pages=args.num_pages,
        paged_attn=args.paged_attn, sparse_reads=args.sparse_reads,
        speculative=args.speculative, draft_layers=args.draft_layers,
        prefix_cache=args.prefix_cache,
        default_cfg_scale=args.cfg_scale,
        preview_every=args.preview_every,
        stream_max_events=args.stream_max_events,
        replicas=args.replicas, mesh_devices=args.mesh_devices,
        replica_roles=(args.replica_roles.split(",")
                       if args.replica_roles else None),
        weights_version=f"{args.name}_dalle@{args.dalle_epoch}",
        # the documented default: --max_replicas 0 means NO runtime
        # growth beyond --replicas, not "uncapped" — cap at the
        # startup width so a scripted add loop cannot exhaust HBM
        max_replicas=args.max_replicas or args.replicas,
        autoscale=autoscale,
        admin_token=args.admin_token or None,
        load_weights=load_weights,
        heartbeat_s=args.heartbeat_s,
        isolation=args.isolation,
        child_rss_limit_mb=args.child_rss_limit_mb,
        transport=args.transport, worker_endpoint=args.worker_endpoint,
        worker_cmd=args.worker_cmd, attach_token=args.attach_token,
        worker_ckpt=args.worker_ckpt,
        worker_use_ema=bool(args.worker_ckpt) and args.use_ema,
        worker_quantize=args.quantize if args.worker_ckpt else "none",
        clip_params=clip_params, clip_cfg=clip_cfg, metrics=metrics,
        log_every=args.log_every, encode=vocab.encode,
        profile_dir=args.profile_dir or None,
        init_deadline_s=args.init_deadline_s,
        init_retries=args.init_retries).start()

    if args.gateway:
        # the fleet-of-fleets tier: N independent cells behind one
        # prefix-affine, tenant-aware front door (docs/SERVING.md
        # "Gateway tier")
        from dalle_pytorch_tpu.serve.gateway import (
            Gateway, serve_gateway_http)
        from dalle_pytorch_tpu.serve.kv_pool import pages_for
        from dalle_pytorch_tpu.serve.tenancy import TenantTable
        if args.autoscale:
            raise SystemExit(
                "--gateway does not compose with --autoscale: each "
                "cell would need its own policy; run cells directly "
                "to autoscale them")
        n_cells = max(args.cells, 1)
        cells = [build_server() for _ in range(n_cells)]
        tenants = TenantTable.from_file(args.tenants) \
            if args.tenants else None
        page_size = args.page_size or 16
        gw = Gateway(
            cells, tenants=tenants, cfg=cfg,
            model_version=f"{args.name}_dalle@{args.dalle_epoch}",
            quantized=args.quantize == "int8_kv",
            queue_depth=args.queue_depth,
            max_prompt_len=cfg.text_seq_len,
            # a request's worst-case fleet-wide page residency: its
            # whole padded sequence, the unit the tenant page budgets
            # meter (dense cells still meter the equivalent)
            pages_per_request=pages_for(cfg.seq_len, page_size),
            admin_token=args.admin_token or None).start()
        tenant_desc = (f", tenants {sorted(tenants.names())}"
                       if tenants is not None else ", anonymous tenant")
        say(f"gateway over {n_cells} cells ({args.replicas} replica(s) "
            f"x {args.num_slots} slots each) on "
            f"http://{args.host}:{args.port}{tenant_desc}")
        say(f"admin: POST /admin/tenants with Authorization: Bearer "
            f"{gw.admin_token} hot-reloads the tenant table; "
            f"GET /stats /metrics /tenants for the fleet surface")
        return args, gw, serve_gateway_http

    server = build_server()
    kv_desc = args.kv if args.kv == "dense" \
        else f"{args.kv}/{args.paged_attn}" \
        + ("/sparse_reads" if args.sparse_reads else "") \
        + ("/prefix_cache" if args.prefix_cache else "")
    if args.speculative:
        kv_desc += (f", speculative k={args.speculative}"
                    f"/d={args.draft_layers or 'depth/2'}")
    if args.cfg_scale > 0:
        kv_desc += f", cfg_scale={args.cfg_scale:g}"
    iso_desc = args.isolation if args.transport == "pipe" \
        else f"{args.isolation}/{args.transport}"
    if args.replica_roles:
        iso_desc += f" [{args.replica_roles}]"
    mesh_desc = "" if args.mesh_devices <= 1 \
        else f" x {args.mesh_devices}-device mesh"
    say(f"serving {dalle_path} on http://{args.host}:{args.port} "
        f"({args.replicas} {iso_desc} replica(s){mesh_desc} x "
        f"{args.num_slots} slots, K={args.chunk_steps}, kv={kv_desc}, "
        f"queue {args.queue_depth})")
    prof_desc = (f"; POST /admin/profile -> {args.profile_dir}"
                 if args.profile_dir else "")
    say(f"observability: GET /metrics (Prometheus exposition), "
        f"GET /debug/events (flight recorder), per-request trace "
        f"summaries on every result{prof_desc} — "
        f"docs/OBSERVABILITY.md")
    if args.transport == "socket" and args.replicas > 1:
        listener = server.engine.listener
        say(f"worker endpoint {listener.advertise_endpoint} — attach "
            f"a worker with: DALLE_WORKER_TOKEN={listener.token} "
            f"python -m dalle_pytorch_tpu.serve.worker --connect "
            f"{listener.advertise_endpoint} --index N")
    if server._is_set:
        scale_desc = "" if not args.max_replicas \
            else f", max_replicas {args.max_replicas}"
        auto_desc = "" if autoscale is None \
            else (f", autoscaler {autoscale.min_replicas}.."
                  f"{autoscale.max_replicas}")
        say(f"admin: POST /admin/scale with Authorization: Bearer "
            f"{server.admin_token}{scale_desc}{auto_desc} — e.g. "
            f"curl -s localhost:{args.port}/admin/scale -H "
            f"'Authorization: Bearer {server.admin_token}' -d "
            f"'{{\"op\": \"status\"}}'")
    return args, server, serve_http


def main(argv=None):
    args, front, serve_loop = start(argv)
    serve_loop(front, args.host, args.port)


if __name__ == "__main__":
    main()

"""Serving front-end: the threaded Python API and the stdlib HTTP server.

``InferenceServer`` wires the three pipeline stages together —
``scheduler.RequestQueue`` (admission) -> ``engine.Engine`` (slot-batched
decode, its own thread) -> ``postprocess.PostProcessor`` (VAE/CLIP, its
own thread) — and owns their lifecycle. Backend bring-up goes through the
SAME deadline/backoff/jitter discipline as every other entry point
(``resilience.retry``): a wedged TPU claim surfaces as a structured
``BringupError`` instead of a hung server.

Two call surfaces:
  * Python: ``submit(codes, ...) -> RequestHandle`` / ``stats()`` — what
    tests, the bench, and embedders use;
  * HTTP (``serve_http``): POST /generate {"codes": [...] | "caption":
    "...", sampling knobs...} blocks for the result (429 on queue-full,
    504 on deadline, both with the structured record as the JSON body);
    GET /stats and /healthz for operators. The stdlib ThreadingHTTPServer
    is deliberate — one dependency-free front-end; a production mesh
    would sit a real gateway in front of the same Python API.
"""

from __future__ import annotations

import json
import os
import sys
import threading
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Callable, List, Optional

from dalle_pytorch_tpu.serve import engine as engine_mod
from dalle_pytorch_tpu.serve import postprocess as post_mod
from dalle_pytorch_tpu.serve import scheduler as S


class InferenceServer:
    """Continuous-batching text->image service. ``replicas=1`` (the
    default) runs one engine on one thread; ``replicas=N`` fronts N
    supervised engine replicas with the same shared queue through
    ``serve.replica.ReplicaSet`` — replica crash/hang/drain fails over
    with zero lost requests via deterministic replay, and capacity loss
    degrades to typed ``QueueFull`` backpressure (docs/SERVING.md
    'Replica set & failover'). ``isolation='process'`` additionally
    runs each replica's engine in a supervised child process, so a
    SIGSEGV/SIGKILL/OOM kill of one replica cannot take the server
    down (docs/SERVING.md 'Process isolation'); /healthz then reports
    per-replica PID, restart count, last exit signal, and child RSS,
    503 still only when ALL replicas are dead."""

    def __init__(self, params: dict, vae_params: dict, cfg, *,
                 num_slots: int = 4, queue_depth: int = 64,
                 chunk_steps: int = 8,
                 prefill_buckets=None,
                 quantize_cache: bool = False,
                 kv: str = "dense",
                 page_size: int = 0,
                 num_pages: int = 0,
                 paged_attn: str = "gather",
                 sparse_reads: bool = False,
                 speculative: int = 0,
                 draft_layers: int = 0,
                 prefix_cache: bool = False,
                 preview_every: int = 0,
                 stream_max_events: int = 256,
                 default_cfg_scale: float = 0.0,
                 replicas: int = 1,
                 replica_roles=None,
                 mesh_devices: int = 1,
                 weights_version: str = "0",
                 max_replicas: int = 0,
                 autoscale=None,
                 admin_token: Optional[str] = None,
                 load_weights: Optional[Callable] = None,
                 heartbeat_s: float = 5.0,
                 isolation: str = "thread",
                 child_rss_limit_mb: int = 0,
                 transport: str = "pipe",
                 worker_endpoint: str = "127.0.0.1:0",
                 worker_cmd: Optional[str] = None,
                 attach_token: Optional[str] = None,
                 worker_ckpt: Optional[str] = None,
                 worker_use_ema: bool = False,
                 worker_quantize: str = "none",
                 clip_params: Optional[dict] = None, clip_cfg=None,
                 decode_images: bool = True,
                 metrics=None, log_every: int = 50,
                 profile_dir: Optional[str] = None,
                 encode: Optional[Callable[[str], List[int]]] = None,
                 init_deadline_s: float = 0.0, init_retries: int = 3):
        self.cfg = cfg
        self.metrics = metrics
        self.encode = encode
        # default sink for POST /admin/profile (a request body may name
        # its own dir; with neither, the capture is a typed refusal)
        self.profile_dir = profile_dir or None
        # server-wide guidance default: a request that doesn't carry
        # its own cfg_scale samples with this one (0 = unguided)
        self.default_cfg_scale = float(default_cfg_scale)
        if self.default_cfg_scale < 0:
            raise ValueError(f"default_cfg_scale must be >= 0, got "
                             f"{default_cfg_scale}")
        self.init_deadline_s = init_deadline_s
        self.init_retries = init_retries
        self.replicas = int(replicas)
        # the elastic operator surface (docs/SERVING.md 'Elastic
        # fleet'): POST /admin/scale authenticates against this token
        # (generated when the caller supplies none — printed by the
        # CLI, never guessable), add/remove/drain/upgrade delegate to
        # the replica set, and an AutoscalePolicy drives the same
        # calls off the load signals. A single-replica server with
        # autoscale or a max_replicas headroom cap still fronts a
        # ReplicaSet — elasticity needs supervised slots to grow into.
        import secrets as _secrets
        self.admin_token = admin_token or _secrets.token_hex(16)
        self.autoscale_policy = autoscale
        self.autoscaler = None
        self.load_weights = load_weights
        self.weights_version = str(weights_version)
        self.max_replicas = int(max_replicas)
        self._is_set = (self.replicas > 1 or autoscale is not None
                        or self.max_replicas > 1)
        self.replica_roles = tuple(replica_roles) if replica_roles \
            else None
        if self.replica_roles and not self._is_set:
            # a lone engine has nobody to migrate warm requests to —
            # the disaggregated shape needs a set
            raise ValueError("replica_roles requires a replica set "
                             "(replicas >= 2)")
        if autoscale is not None:
            # the policy caps and the set cap must agree, or the
            # autoscaler would ask for replicas the set typed-rejects
            self.max_replicas = max(self.max_replicas,
                                    autoscale.max_replicas)
        self.mesh_devices = int(mesh_devices)
        if self.mesh_devices < 1:
            raise ValueError(f"mesh_devices must be >= 1, got "
                             f"{mesh_devices}")
        if worker_ckpt is not None and transport != "socket":
            # same silent-misconfiguration hazard as worker_cmd: the
            # operator believes workers load locally when they don't.
            # (socket itself already implies process isolation and
            # replicas >= 2 via the checks below)
            raise ValueError(
                "worker_ckpt requires transport='socket' — its point "
                "is that a worker loads the checkpoint from its OWN "
                "host's store instead of receiving params over a pipe")
        if isolation == "process" and self.replicas < 2:
            # process isolation exists to keep the SET alive through a
            # child death; a 1-replica process set is legal for the
            # ReplicaSet API (restart-with-replay), but the server's
            # contract is replicas>1 — fail loudly instead of serving a
            # shape the operator almost certainly didn't mean
            raise ValueError("isolation='process' requires replicas >= 2")
        if transport != "pipe" and isolation != "process":
            # a transport only exists between a parent and worker
            # processes; silently ignoring the flag would let an
            # operator believe they were host-isolated when they weren't
            raise ValueError(
                f"transport={transport!r} requires isolation='process'")
        if worker_cmd is not None and self.replicas < 2:
            # the single-engine path would drop the launcher command on
            # the floor — same silent-misconfiguration hazard as above
            raise ValueError("worker_cmd requires replicas >= 2 with "
                             "isolation='process' and "
                             "transport='socket'")
        self.isolation = str(isolation)

        self.queue = S.RequestQueue(
            max_depth=queue_depth,
            # a prompt the slot pool can't hold is rejected HERE (typed
            # InvalidRequest / HTTP 400), before it can reach the engine
            max_prompt_len=cfg.text_seq_len,
            # submit-time rejects land in the flight ring (always on)
            # AND the JSONL sink (when configured) — self.engine exists
            # by the first runtime submit
            on_event=self._queue_event)
        if self._is_set:
            from dalle_pytorch_tpu.serve import replica as replica_mod
            self.engine = replica_mod.ReplicaSet(
                params, cfg, self.queue, replicas=self.replicas,
                num_slots=num_slots, chunk_steps=chunk_steps,
                prefill_buckets=prefill_buckets,
                complete=self._on_decoded, metrics=metrics,
                log_every=log_every, quantize_cache=quantize_cache,
                kv=kv, page_size=page_size, num_pages=num_pages,
                paged_attn=paged_attn, sparse_reads=sparse_reads,
                speculative=speculative, draft_layers=draft_layers,
                prefix_cache=prefix_cache, preview_every=preview_every,
                heartbeat_s=heartbeat_s, isolation=isolation,
                child_rss_limit_mb=child_rss_limit_mb,
                transport=transport, worker_endpoint=worker_endpoint,
                worker_cmd=worker_cmd, attach_token=attach_token,
                worker_ckpt=worker_ckpt,
                worker_use_ema=worker_use_ema,
                worker_quantize=worker_quantize,
                devices_per_replica=self.mesh_devices,
                weights_version=self.weights_version,
                max_replicas=self.max_replicas,
                roles=self.replica_roles)
            if self.autoscale_policy is not None:
                from dalle_pytorch_tpu.serve.autoscale import Autoscaler
                # the set's RecordingMetrics: every autoscale_decision
                # lands in the set-level flight ring (and the JSONL
                # sink when one exists) — "why did the fleet reshape"
                # is answerable from /debug/events alone
                self.autoscaler = Autoscaler(
                    self.engine, self.autoscale_policy,
                    metrics=self.engine.metrics)
        elif self.mesh_devices > 1:
            # ONE logical engine pjit-sharded over a device mesh — the
            # serve surface is identical (docs/SERVING.md 'Mesh-sharded
            # engine'), so the single-engine thread loop below drives it
            # unchanged
            import jax

            from dalle_pytorch_tpu.serve.mesh_engine import MeshEngine
            from dalle_pytorch_tpu.parallel import serve_specs as SS
            self.engine = MeshEngine(
                params, cfg, self.queue,
                devices=SS.slice_devices(jax.devices(), 0,
                                         self.mesh_devices),
                num_slots=num_slots,
                chunk_steps=chunk_steps, prefill_buckets=prefill_buckets,
                complete=self._on_decoded, metrics=metrics,
                log_every=log_every, quantize_cache=quantize_cache,
                kv=kv, page_size=page_size, num_pages=num_pages,
                paged_attn=paged_attn, sparse_reads=sparse_reads,
                speculative=speculative, draft_layers=draft_layers,
                prefix_cache=prefix_cache, preview_every=preview_every,
                weights_version=self.weights_version,
                model_version=self.weights_version)
        else:
            self.engine = engine_mod.Engine(
                params, cfg, self.queue, num_slots=num_slots,
                chunk_steps=chunk_steps, prefill_buckets=prefill_buckets,
                complete=self._on_decoded, metrics=metrics,
                log_every=log_every, quantize_cache=quantize_cache,
                kv=kv, page_size=page_size, num_pages=num_pages,
                paged_attn=paged_attn, sparse_reads=sparse_reads,
                speculative=speculative, draft_layers=draft_layers,
                prefix_cache=prefix_cache, preview_every=preview_every,
                weights_version=self.weights_version,
                model_version=self.weights_version)

        # the postprocess stage is built AFTER the engine(s) so its
        # structured events tee into the same flight ring the engine's
        # do (RecordingMetrics — docs/OBSERVABILITY.md)
        self.post = None
        if decode_images:
            self.post = post_mod.PostProcessor(
                params, vae_params, cfg, clip_params=clip_params,
                clip_cfg=clip_cfg, metrics=self.engine.metrics,
                on_fulfill=self._record_latency)

        # -- streaming & fan-out plumbing (docs/SERVING.md 'Streaming,
        # fan-out & variable resolution') --------------------------------
        # progressive previews need somewhere to decode pixels: the
        # engine's harvest-side hook hands the image-token prefix to the
        # postprocess worker (best-effort, never blocking the engine)
        self.stream_max_events = int(stream_max_events)
        self.preview_every = int(preview_every)
        if self.post is not None and preview_every:
            self.engine.on_preview = self.post.submit_preview
        # live registries swept lazily at stats() time: sinks whose
        # channel hasn't ended (streams_active) and group futures not
        # yet assembled (groups_in_flight). Completed paged+prefix
        # groups credit fanout_pages_saved — the COW dividend: pages
        # the siblings' prompt spans would have cost as N cold prefills
        self._streams: list = []
        self._groups: list = []
        self._stream_lock = threading.Lock()
        self.fanout_pages_saved = 0
        self.groups_completed = 0
        self._page_size = int(page_size) or (min(16, cfg.seq_len)
                                             if kv == "paged" else 0)
        self._cow_sharing = (kv == "paged" and prefix_cache)

        # /metrics exposition (obs/registry.py): the sliding-window
        # latency histograms, labeled per weights_version so a rolling
        # upgrade's two generations are distinguishable on a dashboard.
        # Counters/gauges are projected from the live /stats dicts at
        # scrape time — one source of truth, no second set of state.
        from dalle_pytorch_tpu.obs import registry as obs_registry
        self.registry = obs_registry.Registry()
        self.hist_e2e = self.registry.histogram(
            "dalle_serve_e2e_latency_seconds",
            "End-to-end latency of successful requests "
            "(submit -> caller-visible fulfilment)")
        self.hist_queue_wait = self.registry.histogram(
            "dalle_serve_queue_wait_seconds",
            "Queue wait of successful requests (submit -> admission)")
        self.hist_prefill = self.registry.histogram(
            "dalle_serve_prefill_seconds",
            "Prefill/admission span per successful request "
            "(pop -> slotted; trace span prefill_admit)",
            buckets=(0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05,
                     0.1, 0.25, 0.5, 1.0, 2.5, 5.0))
        self.hist_ms_per_token = self.registry.histogram(
            "dalle_serve_decode_ms_per_token",
            "Decode milliseconds per generated token, per successful "
            "request",
            buckets=(0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 25.0,
                     50.0, 100.0, 250.0, 1000.0))
        self.hist_migration = self.registry.histogram(
            "dalle_serve_migration_seconds",
            "Wall seconds per successful live slot migration "
            "(export -> installed on the target)",
            buckets=(0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1,
                     0.25, 0.5, 1.0, 2.5, 5.0, 10.0))
        # serializes /admin/profile's sibling-capture check + arm (two
        # concurrent POSTs targeting different thread-mode replicas
        # must not both pass the per-process-singleton guard)
        self._profile_arm_lock = threading.Lock()
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None

    # -- stage glue ---------------------------------------------------------

    def _queue_event(self, rec: dict) -> None:
        fl = getattr(self.engine, "flight", None)
        if fl is not None:
            fl.record(rec)
        if self.metrics is not None:
            self.metrics.event(**rec)

    def _record_latency(self, result: S.Result) -> None:
        # successful completions only: mixing in error results (whose
        # wait ends early) would deflate the percentiles exactly when a
        # failing dependency makes the tail matter most
        if not result.ok:
            return
        # histogram feed: exactly once per DELIVERED request (this hook
        # runs at the single fulfilment funnel), so the e2e histogram's
        # count equals distinct delivered requests — the /metrics
        # acceptance contract. weights_version labels keep a rolling
        # upgrade's generations separable.
        v = result.weights_version or ""
        self.hist_e2e.observe(result.total_s, weights_version=v)
        self.hist_queue_wait.observe(result.queued_s, weights_version=v)
        if result.tokens is not None and result.decode_s > 0:
            self.hist_ms_per_token.observe(
                1e3 * result.decode_s / max(len(result.tokens), 1),
                weights_version=v)
        tr = result.trace
        if tr is not None:
            prefill = sum(s["total_s"] for s in tr.get("spans", ())
                          if s.get("name") == "prefill_admit")
            if prefill > 0:
                self.hist_prefill.observe(prefill, weights_version=v)

    def _on_decoded(self, handle: S.RequestHandle,
                    result: S.Result) -> None:
        if self.post is not None:
            # latency is recorded by the postprocess stage's on_fulfill,
            # AFTER VAE/CLIP time lands in total_s — the percentiles must
            # describe what the caller actually waited for
            self.post.submit(handle, result)
        else:
            tr = getattr(handle, "trace", None)
            if tr is not None and result.trace is None:
                # summarize before the histogram feed (same rule as
                # PostProcessor._fulfill): _record_latency reads
                # result.trace for the prefill span
                result.trace = tr.summary()
            self._record_latency(result)
            handle.fulfill(result)

    # -- lifecycle ----------------------------------------------------------

    def start(self) -> "InferenceServer":
        """Claim the backend (deadline-bounded, retried with backoff) and
        launch the engine + postprocess threads."""
        from dalle_pytorch_tpu.resilience import retry as rretry

        def claim(attempt):
            from dalle_pytorch_tpu.resilience import faults
            faults.maybe_activate_from_env()
            faults.on_backend_init(attempt)
            import jax
            return jax.devices()

        policy = rretry.RetryPolicy(
            max_attempts=max(self.init_retries, 1),
            deadline_s=self.init_deadline_s or None)
        rretry.retry_with_backoff(
            claim, policy, label="serve_backend_init",
            on_event=(lambda rec: self.metrics.resilience(
                rec.get("kind", "bringup_retry"),
                **{k: v for k, v in rec.items()
                   if k not in ("time", "event", "kind")})
            ) if self.metrics is not None else None)
        # the device this server actually got — logged once, and carried
        # by /healthz: a jax that fell back to the CPU must be visible
        # in the first line of the log, not inferred from latency
        from dalle_pytorch_tpu.utils.device import describe_device
        print(f"[serve] device: {describe_device()}", file=sys.stderr,
              flush=True)

        if self.post is not None:
            self.post.start()
        if self._is_set:
            self.engine.start()     # per-replica threads + supervisor
            if self.autoscaler is not None:
                self.autoscaler.start()
        else:
            self._thread = threading.Thread(
                target=self.engine.run, args=(self._stop,), daemon=True,
                name="serve-engine")
            self._thread.start()
        return self

    def close(self, timeout: float = 30.0) -> None:
        """Close the queue (a submit racing shutdown gets a typed
        ``QueueClosed`` instead of landing after the drain and hanging
        its caller), stop the engine(s) — the replica path joins EVERY
        replica thread with its share of the deadline, and a replica
        outliving its join is fenced so it cannot fulfil or requeue
        later — then drain the shared queue ONCE and cancel everything
        still queued AND everything mid-decode in a slot (typed results
        — the no-hangs contract holds through shutdown for admitted
        requests too), then drain the postprocess stage. The drain runs
        AFTER the engines stop, so a straggler's late requeue lands on
        the drained queue and is fulfilled ``cancelled`` on the spot
        instead of stranding its caller."""
        self.queue.close()
        self._stop.set()
        if self.autoscaler is not None:
            self.autoscaler.close()     # no reshapes during teardown
        if self._is_set:
            self.engine.close(timeout)
        elif self._thread is not None:
            self._thread.join(timeout)
        for handle in self.queue.drain():
            handle.fulfill(S.Result(
                status=S.CANCELLED,
                request_id=handle.request.request_id,
                reason="server shutdown"))
        # after the engine thread stopped: slots still holding requests
        # would otherwise leave their callers blocked in result()
        # (the replica path cancelled its in-slot handles in close())
        if not self._is_set:
            self.engine.cancel_active("server shutdown")
        if self.post is not None:
            self.post.close(timeout)

    # -- the Python API -----------------------------------------------------

    def submit(self, codes, *, seed: int = 0, temperature: float = 1.0,
               filter_thres: float = 0.5, top_p: float = 0.0,
               priority: int = 0,
               deadline_s: Optional[float] = None,
               cfg_scale: Optional[float] = None,
               tenant: str = "",
               stream: bool = False,
               n_samples: int = 1,
               image_seq_len_override: int = 0,
               sinks: Optional[list] = None):
        """Enqueue one generation request. Raises a typed, structured
        ``scheduler.ServeRejected`` subclass: ``QueueFull`` on
        backpressure, ``InvalidRequest`` for an empty or over-long
        prompt, ``QueueClosed`` after ``close()``. ``cfg_scale``
        (default: the server's ``default_cfg_scale``) > 0 samples with
        classifier-free guidance — the engine runs a cond/uncond slot
        pair for this request alone; no dedicated engine needed.

        ``stream=True`` attaches a live ``TokenSink`` (the returned
        handle's ``.sink``) fed every harvested chunk; ``n_samples>1``
        admits a best-of-N group and returns a ``GroupFuture`` (handle-
        compatible) whose result carries the CLIP-ranked sample set;
        ``image_seq_len_override`` caps the generated grid for a
        train-free short-resolution draft. All three compose.

        ``sinks`` lets an upstream tier (the gateway) supply its own
        pre-built TokenSinks — one per sample — instead of this server
        creating fresh ones: a replayed dispatch then re-feeds the SAME
        client-facing sinks, whose high-water marks dedupe the replay."""
        if cfg_scale is None:
            cfg_scale = self.default_cfg_scale
        if stream and self.isolation == "process":
            # the child's stand-in handle has no sink — tokens live in
            # another interpreter until the result frame lands, so a
            # "stream" would be a lie. Typed refusal, not a silent
            # downgrade to one-shot.
            record = S.structured_event(
                "serve_reject", reason="stream_process_isolation",
                detail="token streaming requires isolation='thread' — "
                       "a child-process engine's harvest loop cannot "
                       "reach this process's sinks")
            self._queue_event(record)
            raise S.InvalidRequest(record)
        request = S.Request(
            codes=tuple(int(c) for c in codes), seed=seed,
            sampling=S.SamplingParams(temperature=temperature,
                                      filter_thres=filter_thres,
                                      top_p=top_p),
            priority=priority, deadline_s=deadline_s,
            cfg_scale=float(cfg_scale), tenant=str(tenant),
            stream=bool(stream), n_samples=int(n_samples),
            image_seq_len_override=int(image_seq_len_override))
        if request.n_samples > 1:
            from dalle_pytorch_tpu.serve import fanout
            group = fanout.submit_group(
                self.queue, request, metrics=self.metrics,
                max_events=self.stream_max_events, sinks=sinks)
            with self._stream_lock:
                self._groups.append(group)
                if group.sink is not None:
                    self._streams.append(group.sink)
            return group
        sink = sinks[0] if sinks else None
        if request.stream and sink is None:
            from dalle_pytorch_tpu.serve.stream import TokenSink
            sink = TokenSink(max_events=self.stream_max_events,
                             metrics=self.metrics)
        handle = self.queue.submit(request, sink=sink)
        if sink is not None:
            sink.request_id = handle.request.request_id
            with self._stream_lock:
                self._streams.append(sink)
        return handle

    def _sweep_streams(self) -> None:
        """Retire finished streams/groups from the live registries and
        bank each completed group's COW page dividend — called from
        ``stats()`` so the gauges are current at every scrape without a
        dedicated sweeper thread."""
        from dalle_pytorch_tpu.serve import fanout
        with self._stream_lock:
            self._streams = [s for s in self._streams if not s.done]
            still = []
            for g in self._groups:
                if not g.done():
                    still.append(g)
                    continue
                self.groups_completed += 1
                if self._cow_sharing:
                    self.fanout_pages_saved += fanout.group_pages_saved(
                        g.request.n_samples, len(g.request.codes),
                        self._page_size)
            self._groups = still

    def generate(self, codes, timeout: Optional[float] = None,
                 **kwargs) -> S.Result:
        """Synchronous convenience: submit + wait."""
        return self.submit(codes, **kwargs).result(timeout)

    def engine_alive(self) -> bool:
        """True while the serving loop is live (or before start). For a
        replica set: at least ONE replica serving — the set degrades,
        it does not die with a survivor standing."""
        if self._is_set:
            return self.engine.alive()
        return self._thread is None or self._thread.is_alive()

    def health(self) -> dict:
        """The /healthz body: overall liveness plus, for a replica set,
        per-replica state (``running``/``broken``/``drained``,
        heartbeat age) — ``ok`` is False (HTTP 503) only when EVERY
        replica is dead."""
        from dalle_pytorch_tpu.parallel.serve_specs import SERVE_AXIS
        from dalle_pytorch_tpu.utils.device import describe_device
        device = describe_device()
        out = {"ok": self.engine_alive(),
               "platform": device["platform"],
               "device_kind": device["kind"],
               # mesh observability (/healthz satellite): how many
               # devices each replica's engine spans
               "devices_per_replica": self.mesh_devices,
               "mesh_shape": ({SERVE_AXIS: self.mesh_devices}
                              if self.mesh_devices > 1 else None)}
        if self._is_set:
            out["replicas"] = self.engine.replica_states()
            out["weights_version"] = self.engine.weights_version
            out["upgrading"] = self.engine._upgrading
        return out

    # -- the operator scale surface (POST /admin/scale) ---------------------

    def scale(self, op: str, **kwargs) -> dict:
        """One operator reshape: ``add`` / ``remove`` / ``drain`` /
        ``undrain`` / ``upgrade`` / ``status``, delegated to the
        replica set's elastic API. Raises the set's typed errors
        (``ScaleError`` for illegal transitions, ``UpgradeAborted``
        for a failed rollout) — the HTTP facade maps them to status
        codes, Python callers catch them directly."""
        from dalle_pytorch_tpu.serve import replica as R
        from dalle_pytorch_tpu.utils.metrics import structured_event
        if not self._is_set:
            raise R.ScaleError(structured_event(
                "serve_scale_reject", op=op,
                reason="not_a_replica_set"))
        rs = self.engine
        if op == "add":
            index = rs.add_replica(role=str(kwargs.get("role", "both")))
            return {"op": op, "replica": index,
                    "replicas": rs.n_replicas}
        if op == "remove":
            index = int(kwargs["replica"])
            n = rs.remove_replica(index,
                                  drain=bool(kwargs.get("drain", True)))
            return {"op": op, "replica": index, "reclaimed": n,
                    "replicas": rs.n_replicas}
        if op == "drain":
            index = int(kwargs["replica"])
            return {"op": op, "replica": index,
                    "reclaimed": rs.drain_replica(index)}
        if op == "undrain":
            index = int(kwargs["replica"])
            return {"op": op, "replica": index,
                    "ok": rs.undrain_replica(index)}
        if op == "upgrade":
            ckpt = kwargs.get("ckpt")
            version = kwargs.get("version") or str(ckpt)
            if ckpt is None:
                raise R.ScaleError(structured_event(
                    "serve_scale_reject", op=op,
                    reason="upgrade_needs_ckpt"))
            up = dict(version=str(version),
                      canaries=int(kwargs.get("canaries", 2)))
            if rs.worker_ckpt is not None:
                # checkpoint-path attach: the PATH is the upgrade —
                # each worker loads + validates it locally
                up["ckpt"] = str(ckpt)
            else:
                if self.load_weights is None:
                    raise R.ScaleError(structured_event(
                        "serve_scale_reject", op=op,
                        reason="no_weight_loader",
                        detail="server built without load_weights; "
                               "pass params via the Python API"))
                try:
                    up["params"] = self.load_weights(str(ckpt))
                except Exception as e:  # noqa: BLE001 — a wrong or
                    # torn checkpoint path is the MOST likely operator
                    # mistake; it must answer as a typed refusal (the
                    # fleet untouched), never escape the HTTP handler
                    raise R.ScaleError(structured_event(
                        "serve_scale_reject", op=op,
                        reason="weight_load_failed", ckpt=str(ckpt),
                        error=repr(e))) from e
            record = rs.rolling_upgrade(**up)
            self.weights_version = rs.weights_version
            return {"op": op, **record}
        if op == "status":
            return {"op": op, "replicas": rs.replica_states(),
                    "weights_version": rs.weights_version,
                    "upgrading": rs._upgrading,
                    "max_replicas": rs.max_replicas,
                    "scale_outs": rs.scale_outs,
                    "scale_ins": rs.scale_ins,
                    "upgrades": rs.upgrades}
        raise R.ScaleError(structured_event(
            "serve_scale_reject", op=op, reason="unknown_op"))

    def stats(self) -> dict:
        out = self.engine.stats()
        if self._is_set:
            # drain the set's migration wall-time samples into the
            # exposition histogram (the set records, the server exposes)
            samples = self.engine.migration_seconds
            while samples:
                self.hist_migration.observe(samples.pop(0))
        e2e_ps = self.hist_e2e.percentiles((0.50, 0.95, 0.99))
        out.update({
            "requests_submitted": self.queue.submitted,
            # the histogram windows are the ONE latency source of truth
            # (the same samples /metrics exposes and latency_ms reads);
            # one collect+sort per family covers every quantile below
            "p50_latency_s": round(e2e_ps[0.50], 4),
            "p95_latency_s": round(e2e_ps[0.95], 4),
            # operator-facing percentiles off the sliding histogram
            # windows (obs/registry.py) — until now these existed only
            # inside bench sweeps, invisible to a running fleet
            "latency_ms": {
                "e2e": {f"p{int(q * 100)}": round(1e3 * e2e_ps[q], 3)
                        for q in (0.50, 0.95, 0.99)},
                "queue_wait": self.hist_queue_wait.percentiles_ms(),
            },
            "postprocess_pending": (self.post.pending()
                                    if self.post is not None else 0),
        })
        # streaming & fan-out surface (ISSUE 20 satellite): live gauges
        # from the swept registries, lifetime counters from the stages
        self._sweep_streams()
        with self._stream_lock:
            out.update({
                "streams_active": len(self._streams),
                "groups_in_flight": len(self._groups),
                "groups_completed": self.groups_completed,
                "fanout_pages_saved": self.fanout_pages_saved,
            })
        out["preview_frames"] = (self.post.preview_frames
                                 if self.post is not None else 0)
        out["preview_drops"] = (self.post.preview_drops
                                if self.post is not None else 0)
        return out

    # -- /metrics (Prometheus text exposition) ------------------------------

    # (stats_key, metric name, help) — counters are lifetime-monotonic
    # engine/set counters; gauges are point-in-time. Keys absent from a
    # given shape's stats (dense vs paged, single vs set) simply don't
    # render — the catalog is the UNION, docs/OBSERVABILITY.md.
    _COUNTER_METRICS = (
        ("requests_submitted", "dalle_serve_requests_submitted_total",
         "Requests accepted by the admission queue"),
        ("completed", "dalle_serve_requests_completed_total",
         "Requests decoded to completion"),
        ("expired", "dalle_serve_requests_expired_total",
         "Requests that exceeded their deadline (queued or decoding)"),
        ("rejected", "dalle_serve_requests_rejected_total",
         "Typed submit-time rejections (queue full / invalid / closed)"),
        ("tokens_decoded", "dalle_serve_tokens_decoded_total",
         "Distinct delivered image tokens (replay-safe accounting)"),
        ("decode_steps", "dalle_serve_decode_steps_total",
         "Fused decode steps dispatched (chunks x K)"),
        ("harvests", "dalle_serve_harvests_total",
         "Emit-ring device_gets (the only steady-state host syncs)"),
        ("evicted", "dalle_serve_evicted_total",
         "Paged-pool evictions (victims replay token-exact)"),
        ("requeued", "dalle_serve_requeued_total",
         "Requeues from eviction/page-defer/failover"),
        ("prefix_hits", "dalle_serve_prefix_hits_total",
         "Warm prefix-cache admissions (zero prefill FLOPs)"),
        ("failovers", "dalle_serve_failovers_total",
         "Replica fence+reclaim+replay cycles"),
        ("reclaimed", "dalle_serve_reclaimed_total",
         "Requests reclaimed from fenced replicas for replay"),
        ("bringup_failures", "dalle_serve_bringup_failures_total",
         "Replica bring-up attempts that failed (circuit breaker)"),
        ("scale_outs", "dalle_serve_scale_outs_total",
         "Elastic scale-out actions"),
        ("scale_ins", "dalle_serve_scale_ins_total",
         "Elastic scale-in actions"),
        ("upgrades", "dalle_serve_upgrades_total",
         "Completed rolling weight upgrades"),
        ("migrations", "dalle_serve_migrations_total",
         "Live slot migrations completed (drain/scale-in/upgrade/roles)"),
        ("migrate_fallbacks", "dalle_serve_migrate_fallbacks_total",
         "Migrations that fell back to deterministic replay"),
        ("migrated_tokens_saved",
         "dalle_serve_migrated_tokens_saved_total",
         "Tokens live migration avoided re-decoding"),
        ("profiles_taken", "dalle_serve_profiles_taken_total",
         "Completed POST /admin/profile captures"),
        ("prefill_runs", "dalle_serve_prefill_runs_total",
         "Bucket prefill programs dispatched (cold admissions)"),
        ("warm_admits", "dalle_serve_warm_admits_total",
         "Requests admitted from the prefix cache (no prefill)"),
        ("engine_loop_s", "dalle_serve_engine_loop_seconds_total",
         "Seconds the engine thread spent in iterations that did work"),
        ("harvest_wait_s", "dalle_serve_harvest_wait_seconds_total",
         "Seconds of it blocked fetching an emit ring (device-bound)"),
        ("admit_s", "dalle_serve_admit_seconds_total",
         "Seconds of it admitting requests (plan, transfers, prefill)"),
        ("admit_prefill_s", "dalle_serve_admit_prefill_seconds_total",
         "Seconds of admission inside the prefill / warm-admit call"),
        ("deliver_s", "dalle_serve_deliver_seconds_total",
         "Seconds of it delivering harvested tokens on the host"),
        ("chunks_behind_admit", "dalle_serve_chunks_behind_admit_total",
         "Harvested chunks that ran behind an admission's prefill"),
        ("loop_stalls", "dalle_serve_loop_stalls_total",
         "Chunks whose interval passed 3x their class's median"),
        ("loop_stall_s", "dalle_serve_loop_stall_seconds_total",
         "Seconds by which stalled chunks overran that median"),
        ("reaped", "dalle_serve_reaped_total",
         "Slots freed because the handle terminated externally "
         "(stream disconnect, group cancel, hedge loser)"),
        ("preview_frames", "dalle_serve_preview_frames_total",
         "Progressive preview frames decoded and delivered"),
        ("groups_completed", "dalle_serve_groups_completed_total",
         "Best-of-N sample groups assembled to a ranked result"),
        ("fanout_pages_saved", "dalle_serve_fanout_pages_saved_total",
         "KV pages COW prompt sharing saved across completed groups"),
    )
    _GAUGE_METRICS = (
        ("queue_depth", "dalle_serve_queue_depth",
         "Requests waiting in the admission queue(s)"),
        ("active_slots", "dalle_serve_active_slots",
         "Slots currently decoding"),
        ("num_slots", "dalle_serve_num_slots",
         "Total decode slots across live replicas"),
        ("alive_replicas", "dalle_serve_alive_replicas",
         "Replicas currently serving"),
        ("replicas", "dalle_serve_replicas",
         "Replicas in the set (retired excluded)"),
        ("pages_in_use", "dalle_serve_pages_in_use",
         "Physical KV pages mapped (shared pages counted once)"),
        ("pages_free", "dalle_serve_pages_free",
         "KV pages on the free list"),
        ("kv_hbm_bytes", "dalle_serve_kv_hbm_bytes",
         "Resident HBM bytes of the KV store"),
        ("postprocess_pending", "dalle_serve_postprocess_pending",
         "Completions queued for VAE/CLIP postprocess"),
        ("flight_events", "dalle_serve_flight_events",
         "Records currently retained in the flight ring(s)"),
        ("mean_occupancy", "dalle_serve_mean_occupancy",
         "Mean busy slots per dispatched decode step"),
        ("upgrading", "dalle_serve_upgrading",
         "1 while a rolling upgrade owns the fleet"),
        ("profile_active", "dalle_serve_profile_active",
         "1 while a jax.profiler capture is in flight"),
        ("streams_active", "dalle_serve_streams_active",
         "SSE/token streams currently open (a group counts once)"),
        ("groups_in_flight", "dalle_serve_groups_in_flight",
         "Best-of-N sample groups still decoding"),
    )

    def metrics_text(self) -> str:
        """The ``GET /metrics`` page: counters/gauges projected from
        the live /stats dicts (per-replica samples labeled
        ``replica``/``weights_version``/``state``) plus the latency
        histograms. Built per scrape — scrape cost is one stats() walk
        and string assembly, no device syncs."""
        stats = self.stats()
        counters = [(name, help_text, [(None, stats[key])])
                    for key, name, help_text in self._COUNTER_METRICS
                    if stats.get(key) is not None]
        gauges = [(name, help_text, [(None, stats[key])])
                  for key, name, help_text in self._GAUGE_METRICS
                  if stats.get(key) is not None]
        # identity: which weights generation the fleet serves
        version = stats.get("weights_version", self.weights_version)
        gauges.append(("dalle_serve_info",
                       "Serving identity (labels carry the facts)",
                       [({"weights_version": version,
                          "kv": str(stats.get("kv", "")),
                          "isolation": str(stats.get("isolation",
                                                     "thread"))}, 1)]))
        per = stats.get("per_replica") or ()
        if per:
            def rep_samples(key):
                return [({"replica": rec["replica"],
                          "weights_version": rec.get("weights_version",
                                                     ""),
                          "state": rec.get("state", "")}, rec.get(key))
                        for rec in per]
            counters.append((
                "dalle_serve_replica_tokens_decoded_total",
                "Per-replica tokens decoded (live engines only)",
                rep_samples("tokens_decoded")))
            counters.append((
                "dalle_serve_replica_completed_total",
                "Per-replica completed requests",
                rep_samples("completed")))
            gauges.append((
                "dalle_serve_replica_active_slots",
                "Per-replica busy slots", rep_samples("active_slots")))
            gauges.append((
                "dalle_serve_replica_queued",
                "Per-replica routed-but-not-decoding requests",
                rep_samples("queued")))
            gauges.append((
                "dalle_serve_replica_up",
                "1 while the replica is in the running state",
                [({"replica": rec["replica"],
                   "weights_version": rec.get("weights_version", "")},
                  1 if rec.get("state") == "running" else 0)
                 for rec in per]))
        return self.registry.render(counters=counters, gauges=gauges)

    # -- /debug/events (the flight recorder) --------------------------------

    def debug_events(self) -> dict:
        """Everything the flight recorder holds, one endpoint: the
        set-level ring (scale/upgrade/autoscale lifecycle + fence
        events with embedded victim dumps), per-replica rings, and the
        last dump per fenced replica index."""
        if self._is_set:
            return self.engine.debug_events()
        fl = getattr(self.engine, "flight", None)
        return {"server": fl.dump() if fl is not None else [],
                "replicas": {}, "fenced": {},
                "loop": self.engine.loop_ring.dump()}

    # -- POST /admin/profile (serve-side jax.profiler capture) --------------

    def profile(self, log_dir: Optional[str] = None, chunks: int = 8,
                replica: int = 0) -> dict:
        """Arm a ``jax.profiler`` capture over the next ``chunks`` fused
        decode chunks of one engine (``Engine.request_profile``).
        ``log_dir`` defaults to the server's ``profile_dir``
        (``serve_dalle --profile_dir``); neither set is a typed
        refusal. Process-isolated replicas are typed-refused too — the
        child's programs run in another interpreter, where this
        process's profiler cannot see."""
        from dalle_pytorch_tpu.serve.engine import ProfileError
        log_dir = log_dir or self.profile_dir
        if not log_dir:
            raise ProfileError(S.structured_event(
                "serve_profile_reject", reason="no_profile_dir",
                detail="pass 'dir' in the request body or start the "
                       "server with --profile_dir"))
        if self._is_set:
            if self.engine.isolation == "process":
                raise ProfileError(S.structured_event(
                    "serve_profile_reject",
                    reason="process_isolation",
                    detail="a child-process engine's programs run in "
                           "another interpreter; profile it from the "
                           "worker host (isolation=thread supports "
                           "in-server capture)"))
            replica = int(replica)
            if not 0 <= replica < len(self.engine.replicas) \
                    or self.engine.replicas[replica].engine is None:
                raise ProfileError(S.structured_event(
                    "serve_profile_reject", reason="no_such_replica",
                    replica=replica))
            eng = self.engine.replicas[replica].engine
        else:
            eng = self.engine
        self._write_scopes(eng, str(log_dir))
        with self._profile_arm_lock:
            if self._is_set:
                # jax.profiler is a PER-PROCESS singleton: in a thread-
                # isolation set every replica engine shares it, so a
                # capture on any sibling must 409 here — the sibling's
                # own per-engine guard can't see it, and a second
                # start_trace would crash that replica's decode step
                for i, r in enumerate(self.engine.replicas):
                    e = r.engine
                    if e is not None and e is not eng \
                            and e.profile_active():
                        raise ProfileError(S.structured_event(
                            "serve_profile_reject",
                            reason="capture_active", replica=i))
            rec = dict(eng.request_profile(str(log_dir), chunks=chunks))
        rec["replica"] = int(replica) if self._is_set else 0
        return rec


    @staticmethod
    def _write_scopes(eng, log_dir: str) -> None:
        """``scopes.json`` beside the capture: the engine's device
        programs' instruction -> scope maps (``Engine.device_scopes``),
        which turn the capture's ``XLA Ops`` events into time by named
        scope. Here, on the HTTP thread, before the capture is armed: a
        compile (a cache hit where the persistent cache is on) must
        neither fall into the capture nor stall the engine thread. A
        failure costs the maps, not the capture."""
        os.makedirs(log_dir, exist_ok=True)
        try:
            maps = eng.device_scopes()
        except Exception as e:  # noqa: BLE001 - the capture still runs
            maps = {"error": repr(e)}
        with open(os.path.join(log_dir, "scopes.json"), "w") as f:
            json.dump(maps, f)


# ---------------------------------------------------------------------------
# HTTP front-end
# ---------------------------------------------------------------------------

_HTTP_STATUS = {S.OK: 200, S.REJECTED: 429, S.DEADLINE_EXCEEDED: 504,
                S.CANCELLED: 503, S.ERROR: 500}


def _result_body(result: S.Result) -> dict:
    body = {"status": result.status, "request_id": result.request_id,
            "reason": result.reason, "queued_s": result.queued_s,
            "decode_s": result.decode_s, "total_s": result.total_s}
    if result.weights_version:
        # which weight generation decoded these tokens — the rolling-
        # upgrade contract's caller-visible half (byte-identical per
        # version), so an HTTP client can audit a mid-upgrade mix
        body["weights_version"] = result.weights_version
    if result.trace is not None:
        # the span-timeline summary (obs/trace.py): where this
        # request's milliseconds went, replay edges included
        body["trace"] = result.trace
    if result.tokens is not None:
        body["tokens"] = [int(t) for t in result.tokens]
    if result.image is not None:
        # pixel grids are bulky as JSON; ship shape + the PNG-side is the
        # CLI's job (cli/serve.py --results_dir). Scores ride along.
        body["image_shape"] = list(result.image.shape)
    if result.clip_score is not None:
        body["clip_score"] = result.clip_score
    if result.samples is not None:
        # best-of-N: the ranked member set, best first — the top-level
        # fields above already describe the winner, so a caller that
        # ignores this key still gets best-of-N semantics for free
        body["samples"] = [_result_body(r) for r in result.samples]
    return body


def make_http_server(server: InferenceServer, host: str = "127.0.0.1",
                     port: int = 8000,
                     request_timeout_s: float = 600.0) -> ThreadingHTTPServer:
    """An HTTP facade over ``server``. POST /generate blocks the client
    connection until its request completes (the threaded stdlib server
    gives each connection its own thread; concurrency is the engine's
    slot pool, not the HTTP layer)."""

    class Handler(BaseHTTPRequestHandler):
        def log_message(self, *args):    # quiet: metrics are the record
            pass

        def _send(self, code: int, body: dict) -> None:
            data = json.dumps(body).encode()
            self.send_response(code)
            self.send_header("Content-Type", "application/json")
            self.send_header("Content-Length", str(len(data)))
            self.end_headers()
            self.wfile.write(data)

        def _send_text(self, code: int, text: str, ctype: str) -> None:
            data = text.encode()
            self.send_response(code)
            self.send_header("Content-Type", ctype)
            self.send_header("Content-Length", str(len(data)))
            self.end_headers()
            self.wfile.write(data)

        def do_GET(self):
            if self.path == "/healthz":
                # health must reflect the serving loop(s), not just
                # this HTTP thread — and for a replica set, per-replica
                # liveness with 503 only when ALL replicas are dead
                body = server.health()
                self._send(200 if body["ok"] else 503, body)
            elif self.path == "/stats":
                self._send(200, server.stats())
            elif self.path == "/metrics":
                # Prometheus text exposition (obs/registry.py): the
                # scrape-able twin of /stats plus the latency
                # histograms (docs/OBSERVABILITY.md metric catalog)
                self._send_text(
                    200, server.metrics_text(),
                    "text/plain; version=0.0.4; charset=utf-8")
            elif self.path == "/debug/events":
                # the flight recorder: last-N structured events + span
                # records per replica, always on — the one endpoint a
                # post-incident "why did p95 spike" starts from
                self._send(200, server.debug_events())
            else:
                self._send(404, {"error": f"no route {self.path}"})

        def _admin_scale(self):
            """POST /admin/scale — the authenticated operator reshape
            endpoint (docs/SERVING.md 'Elastic fleet'): {"op": "add" |
            "remove" | "drain" | "undrain" | "upgrade" | "status",
            ...}. 401 without the admin token (Bearer or
            X-Admin-Token), 409 with the structured record for a typed
            ScaleError/UpgradeAborted — an illegal transition is a
            refusal the operator can read, never a partial state."""
            from dalle_pytorch_tpu.serve import auth
            from dalle_pytorch_tpu.serve import replica as R
            if not auth.check_http(self.headers, server.admin_token):
                self._send(401, {"error": "bad admin token"})
                return
            try:
                n = int(self.headers.get("Content-Length", "0"))
                req = json.loads(self.rfile.read(n) or b"{}")
                if not isinstance(req, dict):
                    raise ValueError(f"body must be a JSON object, "
                                     f"got {type(req).__name__}")
                op = str(req.pop("op"))
            except (ValueError, KeyError, TypeError) as e:
                self._send(400, {"error": f"need a JSON body with "
                                          f"'op': {e}"})
                return
            try:
                self._send(200, server.scale(op, **req))
            except (R.ScaleError, R.UpgradeAborted) as e:
                self._send(409, e.record)
            except (ValueError, KeyError, TypeError) as e:
                self._send(400, {"error": str(e)})

        def _admin_profile(self):
            """POST /admin/profile — authenticated serve-side profiler
            capture: {"dir": ..., "chunks": K, "replica": i}, all
            optional (``dir`` falls back to --profile_dir). 401 without
            the admin token; 409 with the structured record while a
            capture is already active (or the target can't be
            profiled) — kernel tuning on a real chip is one curl away,
            and two operators can't trample each other's traces."""
            from dalle_pytorch_tpu.serve import auth
            from dalle_pytorch_tpu.serve.engine import ProfileError
            if not auth.check_http(self.headers, server.admin_token):
                self._send(401, {"error": "bad admin token"})
                return
            try:
                n = int(self.headers.get("Content-Length", "0"))
                req = json.loads(self.rfile.read(n) or b"{}")
                if not isinstance(req, dict):
                    raise ValueError(f"body must be a JSON object, "
                                     f"got {type(req).__name__}")
                rec = server.profile(
                    log_dir=req.get("dir"),
                    chunks=int(req.get("chunks", 8)),
                    replica=int(req.get("replica", 0)))
            except ProfileError as e:
                self._send(409, e.record)
                return
            except (ValueError, KeyError, TypeError) as e:
                self._send(400, {"error": str(e)})
                return
            self._send(200, rec)

        def do_POST(self):
            if self.path == "/admin/scale":
                self._admin_scale()
                return
            if self.path == "/admin/profile":
                self._admin_profile()
                return
            if self.path != "/generate":
                self._send(404, {"error": f"no route {self.path}"})
                return
            try:
                n = int(self.headers.get("Content-Length", "0"))
                req = json.loads(self.rfile.read(n) or b"{}")
                codes = req.get("codes")
                if codes is None and "caption" in req:
                    if server.encode is None:
                        raise ValueError("server has no vocab; send "
                                         "'codes', not 'caption'")
                    codes = server.encode(req["caption"])
                if not codes:
                    raise ValueError("need non-empty 'codes' or 'caption'")
                kwargs = {k: req[k] for k in
                          ("seed", "temperature", "filter_thres", "top_p",
                           "priority", "deadline_s", "cfg_scale",
                           "stream", "n_samples",
                           "image_seq_len_override")
                          if k in req}
                handle = server.submit(codes, **kwargs)
            except S.InvalidRequest as e:
                self._send(400, e.record)       # caller error, not load
                return
            except S.QueueClosed as e:
                self._send(503, e.record)       # shutting down
                return
            except S.ServeRejected as e:
                self._send(429, e.record)       # backpressure
                return
            except (ValueError, KeyError, TypeError) as e:
                self._send(400, {"error": str(e)})
                return
            sink = getattr(handle, "sink", None)
            if sink is not None:
                self._stream_sse(handle, sink)
                return
            try:
                result = handle.result(timeout=request_timeout_s)
            except TimeoutError as e:
                self._send(504, {"error": str(e)})
                return
            self._send(_HTTP_STATUS.get(result.status, 500),
                       _result_body(result))

        def _stream_sse(self, handle, sink) -> None:
            """The streaming response: server-sent events over a
            chunkless HTTP/1.1 body (no Content-Length; the connection
            close delimits the stream — EventSource-compatible).
            Heartbeat comments keep idle proxies from timing the
            stream out; a torn connection (client gone) cancels the
            request/group on the spot, so the engine's done-handle
            reap frees its slots and pages instead of decoding into
            the void."""
            from dalle_pytorch_tpu.serve import stream as stream_mod
            self.send_response(200)
            self.send_header("Content-Type", "text/event-stream")
            self.send_header("Cache-Control", "no-cache")
            self.send_header("Connection", "close")
            self.end_headers()
            self.close_connection = True
            try:
                for ev in sink.events(heartbeat_s=5.0):
                    self.wfile.write(stream_mod.sse_bytes(ev))
                    self.wfile.flush()
                # the terminal frame: the assembled result (ranked
                # samples for a group), so an SSE client needs no
                # second round-trip to fetch what it just watched
                result = handle.result(timeout=request_timeout_s)
                self.wfile.write(stream_mod.sse_bytes(
                    {"event": "result", **_result_body(result)}))
                self.wfile.flush()
            except (BrokenPipeError, ConnectionResetError, OSError):
                handle.fulfill(S.Result(
                    status=S.CANCELLED,
                    request_id=handle.request.request_id,
                    reason="client disconnected mid-stream"))
            except TimeoutError:
                pass    # stream already delivered everything it had

    httpd = ThreadingHTTPServer((host, port), Handler)
    httpd.daemon_threads = True
    return httpd


def serve_http(server: InferenceServer, host: str = "127.0.0.1",
               port: int = 8000) -> None:
    """Blocking HTTP loop (cli/serve.py's main); Ctrl-C shuts down the
    pipeline cleanly."""
    httpd = make_http_server(server, host, port)
    try:
        httpd.serve_forever()
    except KeyboardInterrupt:
        pass
    finally:
        httpd.server_close()
        server.close()

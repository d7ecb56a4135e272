"""A serving cell: an ``InferenceServer`` built as ``cli/serve.py
build_server()`` builds it (from parameters already on the device),
driven through ``submit()`` by a closed loop of streaming clients, timed
at the clients, and checked by running the plain reference over what was
served.
"""

from __future__ import annotations

import gc
import threading
import time

import numpy as np

from benchmark import build, harness, reference, traffic
from benchmark import weights as W

clock = time.perf_counter


class Served:
    """The server with its clients' records."""

    def __init__(self, cell: harness.Cell, seed: int, quantize: str = "none",
                 broken: str = ""):
        import jax.numpy as jnp

        from dalle_pytorch_tpu.models import dalle as D
        from dalle_pytorch_tpu.serve.server import InferenceServer
        spec = cell.spec
        self.cell, self.seed, self.mix, self.broken = \
            cell, seed, cell.traffic, broken
        self.dims = W.dims_of(cell.config, spec["depth"])
        self.dtype = jnp.dtype(cell.config["param_dtype"])
        self.cfg = build.dalle_config(cell.config, self.dims, spec["flags"])
        self.slots = int(spec["num_slots"])
        params = build.init_fn(self.dims, self.dtype)(W.split_seed(seed))
        if quantize in ("int8", "int8_kv"):       # cli/serve.py --quantize
            params = D.quantize_for_decode(params)
        eng = spec["engine"]
        self.server = InferenceServer(
            params, None, self.cfg, num_slots=self.slots,
            queue_depth=max(64, 4 * self.slots),
            chunk_steps=int(eng["chunk_steps"]), kv=eng["kv"],
            paged_attn=eng["paged_attn"],
            quantize_cache=quantize == "int8_kv", decode_images=False,
            weights_version=f"{cell.name}@{seed}").start()
        del params
        n_clients = int(self.mix["clients_per_slot"]) * self.slots
        self.requests = traffic.requests(
            self.mix, seed, int(self.mix["requests_drawn"]), self.dims)
        self.records = []               # one per request sent, any kind
        self._next = 0
        self._lock = threading.Lock()
        self._stop = threading.Event()
        self._clients = [threading.Thread(target=self._client, daemon=True,
                                          name=f"bench-client-{i}")
                         for i in range(n_clients)]

    # -- one request, followed at the client ---------------------------------

    def _send(self, req: dict, kind: str, draft: int = 0) -> dict:
        greedy = req["greedy"]
        rec = {"kind": kind, "prompt_len": len(req["codes"]), "draft": draft,
               "codes": req["codes"], "t_submit": clock(), "events": [],
               "status": None, "spans": []}
        with self._lock:
            self.records.append(rec)
        handle = self.server.submit(
            req["codes"], seed=req["seed"], temperature=1.0,
            filter_thres=1.0 if greedy else 0.5, stream=True,
            image_seq_len_override=draft)
        rec["handle"] = handle
        return rec

    def _follow(self, rec: dict) -> None:
        handle = rec["handle"]
        for ev in handle.sink.events():
            if ev.get("event") == "tokens":
                toks = ev["tokens"]
                if self.broken == "token_altered" and toks:
                    toks = [(t + 1) % self.dims.num_image_tokens for t in toks]
                rec["events"].append((clock(), int(ev["pos"]), list(toks)))
        result = handle.result(timeout=60.0)
        rec["t_done"] = clock()
        rec["status"] = result.status
        tr = getattr(handle, "trace", None)
        rec["spans"] = tr.spans() if tr is not None else []
        del rec["handle"]

    def _disconnect(self, rec: dict) -> None:
        """The client goes away, as ``serve/server.py _stream_sse`` ends a
        torn connection: the handle is fulfilled ``cancelled`` and the
        engine drops the request from its queue or reaps its slot."""
        from dalle_pytorch_tpu.serve import scheduler as S
        handle = rec.get("handle")
        if handle is not None:
            rec["disconnected"] = True
            handle.fulfill(S.Result(
                status=S.CANCELLED, request_id=handle.request.request_id,
                reason="client disconnect"))

    def _client(self) -> None:
        while not self._stop.is_set():
            with self._lock:
                if self._next >= len(self.requests):
                    return
                req = self.requests[self._next]
                self._next += 1
            try:
                rec = self._send(req, "whole")
                if self._stop.is_set():     # sent as the window closed
                    self._disconnect(rec)
                self._follow(rec)
            except Exception as e:  # noqa: BLE001 - a refused submit is a failure
                with self._lock:
                    self.records.append({"kind": "whole", "status":
                                         f"error:{type(e).__name__}",
                                         "events": [], "spans": []})
                if self._stop.is_set():
                    return

    # -- set-up --------------------------------------------------------------

    def warm_up(self) -> None:
        """One short draft for each prefill bucket the mix's lengths use,
        which also compiles the one decode program."""
        from dalle_pytorch_tpu.serve import scheduler as S
        buckets = self.server.engine.buckets
        used = sorted({S.bucket_for(n, buckets) for n in
                       traffic.prompt_lengths(self.mix,
                                              self.dims.text_seq_len)})
        for b in used:
            req = dict(self.requests[0], codes=self.requests[0]["codes"][:1]
                       * b)
            rec = self._send(req, "warm", draft=int(self.mix["warm_draft"]))
            self._follow(rec)
            if rec["status"] != "ok":
                raise RuntimeError(f"warm-up request failed: {rec['status']}")

    def staggered_wave(self) -> None:
        """Fill the slots with drafts of staggered lengths, start the
        clients behind them, and return when the last draft is done: the
        whole requests that replaced them then sit at spread phases."""
        lengths = traffic.stagger(self.mix, self.slots,
                                  self.dims.image_seq_len)
        wave = []
        for i, draft in enumerate(lengths):
            with self._lock:
                req = self.requests[self._next]
                self._next += 1
            wave.append(self._send(req, "draft", draft=draft))
        followers = [threading.Thread(target=self._follow, args=(r,),
                                      daemon=True) for r in wave]
        for t in followers:
            t.start()
        for t in self._clients:
            t.start()
        for t in followers:
            t.join()
        bad = [r["status"] for r in wave if r["status"] != "ok"]
        if bad:
            raise RuntimeError(f"set-up wave failed: {bad}")

    def drain(self, timeout: float) -> None:
        """The window has closed: the clients send nothing more. A request
        that has delivered nothing yet is disconnected; every request
        that has (its tokens are in the window's count) is followed to
        its end, for at most ``timeout`` seconds."""
        self._stop.set()
        with self._lock:
            records = list(self.records)
        for rec in records:
            if rec["status"] is None and not rec["events"]:
                self._disconnect(rec)
        end = clock() + timeout
        for t in self._clients:
            t.join(timeout=max(end - clock(), 0.0))

    def close(self) -> None:
        self._stop.set()
        self.server.close(timeout=30.0)
        for t in self._clients:
            t.join(timeout=30.0)
        self.server = None


# -- from the clients' records to numbers --------------------------------------

def deliveries(records, t0: float, t1: float):
    """(time, request index, tokens) of every delivery inside [t0, t1)."""
    out = []
    for i, rec in enumerate(records):
        for t, _pos, toks in rec["events"]:
            if t0 <= t < t1:
                out.append((t, i, len(toks)))
    out.sort()
    return out


def tpot_samples(records, t0: float, t1: float) -> list:
    """For every (stream, delivery) in the window but a stream's first:
    milliseconds since that stream's previous delivery, per token."""
    out = []
    for rec in records:
        ev = rec["events"]
        for (ta, _, _), (tb, _, toks) in zip(ev, ev[1:]):
            if t0 <= ta and tb < t1 and toks:
                out.append((tb - ta) * 1e3 / len(toks))
    return out


def harvest_readings(delivs, gap_s: float):
    """Deliveries closer than ``gap_s`` are one harvest. A reading is one
    harvest: (tokens of all streams, seconds since the previous harvest's
    last delivery). The first harvest opens the sequence and has no time."""
    groups = []
    for t, _i, n in delivs:
        if groups and t - groups[-1][0] <= gap_s:
            groups[-1][0] = t
            groups[-1][1] += n
        else:
            groups.append([t, n])
    units = [g[1] for g in groups[1:]]
    seconds = [b[0] - a[0] for a, b in zip(groups, groups[1:])]
    return units, seconds


def whole_stream(rec: dict):
    """The prompt followed by every token delivered, or None where a
    delivery was dropped or replayed (its position is not the next)."""
    seq = list(rec["codes"])
    for _t, pos, toks in rec["events"]:
        if pos != len(seq):
            return None
        seq.extend(toks)
    return seq


def request_failed(rec: dict, dims) -> bool:
    """A whole request has to end ``ok`` with its whole stream delivered:
    every position from the prompt's end to the image's last."""
    if rec["status"] != "ok":
        return True
    seq = whole_stream(rec)
    return seq is None or len(seq) != dims.seq_len


def served_sequences(records, dims, t0, t1, n_check, seed):
    """The requests that finished in the window, all of them up to
    ``n_check`` (beyond that a sample drawn from the seed, the one with
    most served tokens in it; in a window too short to finish one, the
    set-up wave's). -> (sequences, prompt lengths, served counts): each
    sequence the prompt, then the served tokens, padded with zeros to
    seq_len for a draft."""
    done = [r for r in records if r["status"] == "ok" and r["kind"] == "whole"
            and t0 <= r.get("t_done", -1) < t1]
    if not done:
        done = [r for r in records if r["status"] == "ok"
                and r["kind"] == "draft"]
    done = [(r, whole_stream(r)) for r in done]
    done = [(r, s) for r, s in done if s is not None]
    if not done:
        return [], [], []
    done.sort(key=lambda rs: -(len(rs[1]) - rs[0]["prompt_len"]))
    rng = np.random.default_rng([int(seed) & 0xFFFFFFFF, 11])
    rest = list(rng.permutation(len(done) - 1) + 1)[:max(n_check - 1, 0)]
    picked = [done[0]] + [done[i] for i in sorted(rest)]
    seqs, plens, served = [], [], []
    for rec, seq in picked:
        served.append(len(seq) - rec["prompt_len"])
        seqs.append((seq + [0] * dims.seq_len)[:dims.seq_len])
        plens.append(rec["prompt_len"])
    return seqs, plens, served


def check_served(seed, dims, dtype, seqs, plens, served, spec, lower=None):
    """The numbers compared, each beside its limit. The reference runs
    over ``reference_rows`` sequences at a time, so that its logits fit
    whatever the number of requests; the last block is filled up with
    copies of its first row, left out of the comparison, so that the
    reference compiles for one shape only."""
    limits, rows = spec["limits"], int(spec["reference_rows"])
    g = []
    for at in range(0, len(seqs), rows):
        block_seqs, block_lens = seqs[at:at + rows], plens[at:at + rows]
        real = len(block_seqs)
        fill = rows - real
        gaps, mask = reference.served_gaps(
            seed, dims, dtype, block_seqs + block_seqs[:1] * fill,
            block_lens + block_lens[:1] * fill, lower=lower)
        gaps = np.asarray(gaps)[:real]
        mask = np.asarray(mask)[:real].copy()
        for r, (p, n) in enumerate(zip(block_lens, served[at:at + rows])):
            mask[r, p - 1 + n:] = False      # a draft stops early
        g.append(gaps[mask])
    g = np.concatenate(g)
    return [
        {"name": "served_logit_gap_max", "value": float(g.max()),
         "limit": limits["served_logit_gap_max"]},
        {"name": "served_logit_gap_mean", "value": float(g.mean()),
         "limit": limits["served_logit_gap_mean"]},
        {"name": "served_not_best_share", "value": float((g > 0).mean()),
         "limit": limits["served_not_best_share"]},
    ], int(g.size)


def measure(cell, args, seed, seconds, listener, trace_on, quantize="none",
            drain=True):
    """Set a server up from ``seed``, run one window, follow its requests
    to their end (``drain``), close it, check."""
    spec = cell.spec
    t_build = clock()
    served = Served(cell, seed, quantize=quantize, broken=args.broken)
    t_server = clock()
    served.warm_up()
    t_warm = clock()
    served.staggered_wave()
    setup_compile = listener.snapshot()
    stats0 = served.server.stats()
    t_open = clock()
    setup_s = t_open - harness.Clock.start

    trace = None
    if trace_on:
        from benchmark import reduce as R
        trace = R.Capture(cell.name, seed)
        trace.start()
        time.sleep(min(float(spec["trace_seconds"]), seconds))
        trace.stop()
    time.sleep(max(t_open + seconds - clock(), 0.0))
    t_close = clock()
    stats1 = served.server.stats()
    in_window = listener.snapshot()
    peak = harness.memory_peak_bytes()
    served.drain(float(spec["drain_timeout_s"]) if drain else 0.0)
    t_drained = clock()
    served.close()
    records = served.records
    dims, dtype = served.dims, served.dtype
    del served
    gc.collect()    # the engine and its jitted closures refer to each other

    delivs = deliveries(records, t_open, t_close)
    units, secs = harvest_readings(delivs, float(spec["harvest_gap_ms"]) / 1e3)
    tpot = tpot_samples(records, t_open, t_close)
    # attempted: every whole request whose tokens the window counted, the
    # ones that ended in it and the ones the drain followed to their end
    attempted = [r for r in records if r["kind"] == "whole"
                 and not r.get("disconnected")
                 and any(t_open <= t < t_close for t, _p, _k in r["events"])]
    ended = [r for r in attempted if r.get("t_done", t_close) < t_close]
    failed = [r for r in attempted if request_failed(r, dims)] + [
        r for r in records if str(r["status"]).startswith("error")]

    t_ref = clock()
    seqs, plens, n_served = served_sequences(
        records, dims, t_open, t_close, int(spec["check_requests"]), seed)
    checks, compared = [], 0
    if seqs:
        lower = "fp8" if args.control == "reference_fp8" else None
        checks, compared = check_served(seed, dims, dtype, seqs, plens,
                                        n_served, spec, lower)
    ref_s = clock() - t_ref
    return {
        "seed": seed, "records": records, "t_open": t_open,
        "t_close": t_close, "setup_s": setup_s, "units": units,
        "seconds": secs, "tpot": tpot, "ended": len(ended),
        "attempted": len(attempted), "failed": len(failed),
        "checks": checks, "compared": compared, "checked": len(seqs),
        "reference_s": ref_s, "drain_s": t_drained - t_close, "peak": peak,
        "trace": trace,
        "stats0": stats0, "stats1": stats1, "setup_compile": setup_compile,
        "compiles_in_window": in_window["compiles"]
        - setup_compile["compiles"], "dims": dims,
        "setup": {"server_s": t_server - t_build, "warm_s": t_warm - t_server,
                  "wave_s": t_open - t_warm, "total_s": setup_s,
                  **setup_compile},
    }


def run(cell: harness.Cell, args, device: dict, listener) -> str:
    quantize = "int8_kv" if args.control == "program_int8" else "none"
    m = measure(cell, args, args.seed, float(args.seconds), listener,
                bool(args.trace), quantize)
    dims = m["dims"]
    print(f"compared {m['compared']} served tokens of {m['checked']} "
          f"requests; reference took {m['reference_s']:.1f} s, the drain "
          f"{m['drain_s']:.1f} s", flush=True)
    correct = bool(m["checks"]) and harness.print_checks(m["checks"]) \
        and m["failed"] == 0
    per_request = traffic.mean_tokens_per_request(cell.traffic, dims)
    # all the tokens delivered from the window's first harvest to its last,
    # over all that time; the median harvest stands beside it per layer
    tokens_per_s = harness.whole_window_rate(m["units"], m["seconds"])
    e2e = {
        "images_per_s": {"value": tokens_per_s / per_request,
                         "unit": "images/s"},
        "tpot_ms": {"value": harness.median(m["tpot"]), "unit": "ms"},
        "tpot_ms_p95": {"value": harness.percentile(m["tpot"], 95),
                        "unit": "ms"},
        "setup_s": {"value": m["setup_s"], "unit": "s"},
    }
    print(f"tpot samples {len(m['tpot'])}, harvest readings "
          f"{len(m['units'])}, requests ended in the window {m['ended']}, "
          f"followed to their end after it {m['attempted'] - m['ended']}, "
          f"failed {m['failed']}", flush=True)
    payload = {
        "cell": cell.name, "seed": args.seed,
        "harvest_tokens": m["units"], "harvest_seconds": m["seconds"],
        "tpot_ms_samples": m["tpot"],
        "whole_window_tokens_per_s": tokens_per_s,
        "median_of_readings_tokens_per_s":
        harness.rate_from_readings(m["units"], m["seconds"]),
        "tokens_per_request": per_request, "setup": m["setup"],
        "checks": m["checks"], "reference_s": m["reference_s"],
        "requests_ended": m["ended"], "requests_attempted": m["attempted"],
        "requests_failed": m["failed"], "drain_s": m["drain_s"],
    }
    harness.write_readings(cell.name, args.seed, args.trace, payload)

    for k in range(int(args.more_seeds)):
        # more seeds after one process start-up: a short window each,
        # program and control read side by side (limits are set from these)
        extra = args.seed + 1 + k
        mm = measure(cell, args, extra, float(cell.spec["check_window_s"]),
                     listener, False, quantize, drain=False)
        print(f"seed {extra}: " + "; ".join(
            f"{c['name']} {c['value']:.6g}" for c in mm["checks"]),
            flush=True)

    device = dict(device, memory_peak_bytes=m["peak"])
    ctx = {"cell": cell, "dims": dims, "kind": "serve", "readings": payload,
           "records": m["records"], "t_open": m["t_open"],
           "t_close": m["t_close"], "stats0": m["stats0"],
           "stats1": m["stats1"], "setup_compile": m["setup_compile"],
           "compiles_in_window": m["compiles_in_window"],
           "end_to_end": {k: v["value"] for k, v in e2e.items()},
           "device": device, "peaks": harness.peaks_for(device["kind"]),
           "chips": cell.chips, "trace": None,
           "spans": [s for r in m["records"] for s in r["spans"]]}
    breakdown = None
    if m["trace"] is not None:
        red = m["trace"].reduce()
        ctx["trace"] = red
        device.update(busy_s=red.busy_s, window_s=red.window_s)
        breakdown = red.breakdown()
    metrics = harness.read_per_layer(cell, ctx) if args.trace else e2e
    return harness.result_line(
        correct=correct, attempted=m["attempted"], failed=m["failed"],
        metrics=metrics, device=device, breakdown=breakdown)

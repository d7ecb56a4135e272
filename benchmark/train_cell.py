"""A training cell: the trainer's own step, placement and feed, timed in
readings of a few steps with the next reading queued before the last is
fetched, and checked against the plain reference on its first two steps.
"""

from __future__ import annotations

import time

import numpy as np

from benchmark import build, harness, reference, traffic
from benchmark import weights as W

B1, B2, EPS = 0.9, 0.999, 1e-8      # optax.adam's defaults, as the CLI uses


def _norms(tree):
    import jax
    import jax.numpy as jnp
    return jax.tree.map(
        lambda a: jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32)))), tree)


def leaf_gaps(program: dict, ref: dict) -> dict:
    """For every leaf, the gap between its two norms measured against the
    reference's norm of that leaf or of the median leaf, whichever is
    larger (some gradients are all but zero)."""
    import jax
    r = jax.tree.leaves(ref)
    floor = float(np.median(r))
    paths = ["/".join(str(getattr(k, "key", k)) for k in path)
             for path, _ in jax.tree_util.tree_leaves_with_path(ref)]
    return {name: abs(a - b) / max(b, floor)
            for name, a, b in zip(paths, jax.tree.leaves(program), r)}


def worst_leaf_gap(program: dict, ref: dict) -> float:
    return max(leaf_gaps(program, ref).values())


class Trainer:
    """The compiled step with its state: built once in set-up, driven
    through its first steps there, and handed as it is to the window."""

    def __init__(self, cell: harness.Cell, seed: int, broken: str = ""):
        import jax
        import jax.numpy as jnp
        import optax
        from jax.sharding import NamedSharding, PartitionSpec as P

        from dalle_pytorch_tpu.cli.common import step_rng
        from dalle_pytorch_tpu.models import dalle as D
        from dalle_pytorch_tpu.parallel import make_mesh, shard_batch
        from dalle_pytorch_tpu.parallel.train import (dalle_param_specs,
                                                      make_train_step,
                                                      setup_sharded)
        spec, mix = cell.spec, cell.traffic
        self.cell, self.seed, self.mix = cell, seed, mix
        self.dims = W.dims_of(cell.config, spec["depth"])
        self.dtype = jnp.dtype(cell.config["param_dtype"])
        self.cfg = build.dalle_config(cell.config, self.dims, spec["flags"])
        devices = jax.devices()[:cell.chips]
        self.mesh = make_mesh(spec.get("mesh") or {"dp": cell.chips}, devices)
        self.batch_axis = spec.get("batch_axis", "dp")
        self.rows = int(mix["rows_per_group"]) \
            * int(self.mesh.shape[self.batch_axis])
        self.lr = float(spec["flags"]["lr"])
        optimizer = optax.adam(self.lr)

        init = W.tree
        halves = W.split_seed(seed)
        shapes = jax.eval_shape(
            lambda h: init(h, self.dims, self.dtype), halves)
        axes = spec.get("param_axes")
        self.specs = dalle_param_specs(shapes, mesh=self.mesh, **axes) \
            if axes else None
        shardings = jax.tree.map(
            lambda s: NamedSharding(self.mesh, s), self.specs,
            is_leaf=lambda x: isinstance(x, P)) if axes \
            else NamedSharding(self.mesh, P())
        self._init = jax.jit(lambda h: init(h, self.dims, self.dtype),
                             out_shardings=shardings)
        self.halves = halves
        params = self._init(halves)
        self.params, self.opt_state = setup_sharded(
            params, optimizer, self.mesh, self.specs)

        cfg = self.cfg

        def loss_fn(p, batch, rng):
            # the trainer's own loss (cli/train_dalle.py): all-True mask
            text = batch["text"]
            return D.dalle_apply(p, text, batch["image"], cfg=cfg,
                                 mask=jnp.ones_like(text, bool), rng=rng,
                                 train=True, return_loss=True)

        step = make_train_step(loss_fn, optimizer)
        if broken == "state_unchanged":
            real = step
            step = lambda p, o, b, r: (p, o, real(  # noqa: E731
                jax.tree.map(jnp.copy, p), jax.tree.map(jnp.copy, o),
                b, r)[2])
        self._step = step
        self._key = jax.random.PRNGKey(int(seed) & 0x7FFFFFFF)
        self._shard = lambda b: shard_batch(self.mesh, b, self.batch_axis)
        self._rng = lambda i: step_rng(self._key, i, self.mesh)
        self.broken = broken
        self.steps_done = 0
        self.tokens_per_step = self.rows * self.dims.seq_len

    def host_batch(self, index: int) -> dict:
        b = traffic.train_batch(self.mix, self.seed, index, self.rows,
                                self.dims)
        if self.broken == "batch_part_left_out":
            # the fault the loss is there to catch: half the rows repeated
            half = self.rows // 2
            b = {k: np.concatenate([v[:half], v[:half]]) for k, v in b.items()}
        return b

    def step(self):
        """One step through the window's own call and feed."""
        import jax
        with jax.profiler.TraceAnnotation("bench.make_batch"):
            host = self.host_batch(self.steps_done)
        with jax.profiler.TraceAnnotation("bench.place_batch"):
            batch = self._shard(host)
            rng = self._rng(self.steps_done)
        with jax.profiler.TraceAnnotation("bench.dispatch_step"):
            self.params, self.opt_state, loss = self._step(
                self.params, self.opt_state, batch, rng)
        self.steps_done += 1
        return loss

    def first_steps(self) -> dict:
        """Steps 1 and 2, with what the reference will be held against."""
        import jax
        import jax.numpy as jnp
        loss0 = float(self.step())
        mu = self.opt_state[0].mu
        gnorm = jax.jit(lambda m: jax.tree.map(
            lambda x: x / (1 - B1), _norms(m)))(mu)
        dnorm = jax.jit(lambda p, h: _norms(jax.tree.map(
            lambda a, b: a.astype(jnp.float32) - b.astype(jnp.float32),
            p, self._init(h))))(self.params, self.halves)
        out = {"grad_norm": jax.tree.map(float, gnorm),
               "change_norm": jax.tree.map(float, dnorm)}
        out["loss"] = [loss0, float(self.step())]
        return out

    def reading(self, steps: int):
        """Dispatch ``steps`` steps; the last loss stands for them."""
        loss = None
        for _ in range(steps):
            loss = self.step()
        return loss

    def free(self):
        self.params = self.opt_state = None


def timed_readings(trainer: Trainer, steps: int, stop, sync_every_step=False):
    """Readings of ``steps`` steps until ``stop(seconds so far, elapsed)``.
    The next reading is dispatched before the previous one's loss is
    fetched, so the device always holds queued work; a reading's time
    runs from the previous fetch's return to its own. With
    ``sync_every_step`` nothing is queued ahead of a fetch (the method
    the stall hunt compares with). -> (seconds, fetch times)."""
    import jax
    seconds, stamps = [], []
    clock = time.perf_counter
    float(trainer.reading(steps))       # the window opens on a drained queue
    t_open = last = clock()
    pending = trainer.reading(steps)
    while True:
        nxt = None if sync_every_step else trainer.reading(steps)
        with jax.profiler.TraceAnnotation("bench.fetch_loss"):
            float(pending)
        now = clock()
        seconds.append(now - last)
        stamps.append(now - t_open)
        last = now
        if stop(seconds, now - t_open):
            break
        pending = nxt if nxt is not None else trainer.reading(steps)
    if nxt is not None:
        float(nxt)                      # drain what was queued ahead
    return seconds, stamps


def run(cell: harness.Cell, args, device: dict, listener) -> str:
    spec, mix = cell.spec, cell.traffic
    steps = int(mix["steps_per_reading"])
    t_build = time.perf_counter()
    trainer = Trainer(cell, args.seed, broken=args.broken)
    first = trainer.first_steps()
    t_first = time.perf_counter()

    # warm-up: until five consecutive readings agree, and three seconds
    rel, most = float(spec["warm_agree_rel"]), int(spec["warm_max_readings"])
    warm, _ = timed_readings(
        trainer, steps,
        lambda secs, el: len(secs) >= most or (
            len(secs) >= 5 and el >= 3.0 and harness.agree(secs[-5:], rel)))
    setup_compile = listener.snapshot()
    clock = time.perf_counter
    setup_s = clock() - harness.Clock.start

    window, stamps, trace = [], [], None
    left = float(args.seconds)
    if args.trace:
        # the profiler covers the first part of the window only
        from benchmark import reduce as R
        trace = R.Capture(cell.name, args.seed)
        part = min(float(spec["trace_seconds"]), left)
        trace.start()
        window, stamps = timed_readings(trainer, steps,
                                        lambda secs, el: el >= part)
        trace.stop()
        left -= stamps[-1]
    if left > 0:
        more, at = timed_readings(
            trainer, steps, lambda secs, el: el >= left,
            sync_every_step=bool(args.sync_every_step))
        base = stamps[-1] if stamps else 0.0
        window, stamps = window + more, stamps + [base + t for t in at]
    in_window = listener.snapshot()
    peak = harness.memory_peak_bytes()
    tokens = trainer.tokens_per_step * steps
    # every token of the window over all its time; the median reading
    # stands beside it as a per-layer metric
    rate = harness.whole_window_rate([tokens] * len(window), window)
    steady = harness.rate_from_readings([tokens] * len(window), window)

    # the reference runs once the program's state is freed
    batches = [traffic.train_batch(mix, args.seed, i, trainer.rows,
                                   trainer.dims) for i in (0, 1)]
    dims, dtype, lr = trainer.dims, trainer.dtype, trainer.lr
    trainer.free()
    t_ref = clock()
    lower = "fp8" if args.control == "reference_fp8" else None
    if lower:
        # the control: the reference in the precision below, in the
        # program's place, against the reference itself
        first = reference.train_two_steps(args.seed, dims, dtype, batches,
                                          lr, B1, B2, EPS, lower=lower)
    ref = reference.train_two_steps(args.seed, dims, dtype, batches, lr,
                                    B1, B2, EPS)
    ref_s = clock() - t_ref
    lim = spec["limits"]
    checks = [
        {"name": "loss_rel_gap", "limit": lim["loss_rel_gap"],
         "value": max(abs(a - b) / abs(b)
                      for a, b in zip(first["loss"], ref["loss"]))},
        {"name": "grad_norm_worst_leaf_gap",
         "limit": lim["grad_norm_worst_leaf_gap"],
         "value": worst_leaf_gap(first["grad_norm"], ref["grad_norm"])},
        {"name": "change_norm_worst_leaf_gap",
         "limit": lim["change_norm_worst_leaf_gap"],
         "value": worst_leaf_gap(first["change_norm"], ref["change_norm"])},
    ]
    print(f"losses program {first['loss']} reference {ref['loss']}; "
          f"reference took {ref_s:.1f} s", flush=True)
    for k in ("grad_norm", "change_norm"):
        gaps = leaf_gaps(first[k], ref[k])
        worst = max(gaps, key=gaps.get)
        print(f"worst {k} leaf: {worst} ({gaps[worst]:.4g})", flush=True)
    correct = harness.print_checks(checks)

    payload = {
        "cell": cell.name, "seed": args.seed, "steps_per_reading": steps,
        "tokens_per_reading": tokens, "warm_readings_s": warm,
        "window_readings_s": window, "window_fetch_at_s": stamps,
        "whole_window_tokens_per_s": rate,
        "median_of_readings_tokens_per_s": steady,
        "slowest_reading_s": max(window), "median_reading_s":
        harness.median(window), "sync_every_step": bool(args.sync_every_step),
        "setup": {"build_s": t_first - t_build, "total_s": setup_s,
                  **setup_compile},
        "checks": checks, "reference_s": ref_s,
        "leaves": {k: {"program": first[k], "reference": ref[k],
                       "gap": leaf_gaps(first[k], ref[k])}
                   for k in ("grad_norm", "change_norm")},
    }
    harness.write_readings(cell.name, args.seed, args.trace, payload)

    device = dict(device, memory_peak_bytes=peak)
    ctx = {"cell": cell, "dims": dims, "kind": "train", "readings": payload,
           "setup_compile": setup_compile,
           "compiles_in_window": in_window["compiles"]
           - setup_compile["compiles"],
           "end_to_end": {"train_tokens_per_s": rate, "setup_s": setup_s},
           "device": device, "peaks": harness.peaks_for(device["kind"]),
           "chips": cell.chips, "trace": None, "spans": [], "counters": {}}
    e2e = {"train_tokens_per_s": {"value": rate, "unit": "tokens/s"},
           "setup_s": {"value": setup_s, "unit": "s"}}
    breakdown = None
    if trace is not None:
        red = trace.reduce()
        ctx["trace"] = red
        device.update(busy_s=red.busy_s, window_s=red.window_s)
        breakdown = red.breakdown()
    metrics = harness.read_per_layer(cell, ctx) if args.trace else e2e
    return harness.result_line(
        correct=correct, attempted=len(window) * steps, failed=0,
        metrics=metrics, device=device, breakdown=breakdown)

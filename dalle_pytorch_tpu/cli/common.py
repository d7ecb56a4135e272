"""Shared CLI plumbing: common flags, device/mesh setup, seeding.

The reference scripts configure everything through per-script argparse
(SURVEY.md §5.6); these helpers keep the rebuilt CLIs' flag surface
consistent (same names as the reference where one exists: --batchSize,
--dataPath, --imageSize, --n_epochs, --lr, --name, --start_epoch) and add
the TPU-era flags (--dp mesh, --profile_dir, --nan_checks, --metrics).
"""

from __future__ import annotations

import argparse
import functools
import itertools
import os
from typing import Optional

import jax
import numpy as np

from dalle_pytorch_tpu import checkpoint as ckpt
from dalle_pytorch_tpu.parallel import make_mesh, replicate
from dalle_pytorch_tpu.utils import MetricsLogger, StepProfiler, \
    enable_nan_checks


def say(*parts, **kw) -> None:
    """print() on process 0 only — multi-host pods otherwise echo every
    epoch summary/progress line once per host, interleaved (MetricsLogger
    already gates its per-step output the same way)."""
    from dalle_pytorch_tpu.parallel.multihost import is_primary
    if is_primary():
        print(*parts, **kw)


def resolve_resume(name_or_path: str, models_dir: str, start_epoch: int):
    """Resolve a --loadVAE/--load_dalle value to (checkpoint path,
    start_epoch). A directory path is used as-is; a name with
    ``start_epoch > 0`` maps to ``{models_dir}/{name}-{start_epoch-1}``
    (the reference's explicit-epoch resume, trainVAE.py:20-21); a bare name
    with no start_epoch resumes from the NEWEST checkpoint."""
    if os.path.isdir(name_or_path):
        return name_or_path, start_epoch
    if start_epoch > 0:
        return ckpt.ckpt_path(models_dir, name_or_path,
                              start_epoch - 1), start_epoch
    found = ckpt.latest(models_dir, name_or_path)
    if found is None:
        raise FileNotFoundError(
            f"no checkpoint named {name_or_path!r} under {models_dir!r} "
            "(give --start_epoch to pick a specific epoch)")
    path, epoch = found
    return path, epoch + 1


def plan_resume(args, name: str, explicit: str = "",
                steps_per_epoch: int = 0):
    """Where should this run continue from? Returns None (fresh start) or
    ``{path, start_epoch, skip_batches, global_step, meta, mid_epoch}``.

    ``--auto_resume`` wins: the newest VALID checkpoint (step or epoch,
    ordered by training progress — resilience.find_auto_resume). The data
    stream continues mid-epoch with zero duplicated or skipped steps;
    ``--n_epochs`` keeps the repo-wide meaning of "epochs to run from the
    resume point" (the resumed partial epoch counts as the first), so a
    restart passes the REMAINING epoch count — see the --auto_resume help
    text and docs/RESILIENCE.md. Otherwise an ``explicit``
    --loadVAE/--load_dalle/--load_clip value resolves through
    ``resolve_resume`` as before. ``global_step`` falls back to
    ``start_epoch * steps_per_epoch`` for checkpoints written before the
    meta carried it."""
    if args.auto_resume:
        from dalle_pytorch_tpu.resilience import find_auto_resume
        found = find_auto_resume(args.models_dir, name)
        if found is not None:
            path, manifest = found
            meta = manifest.get("meta", {}) or {}
            if "step_in_epoch" in meta and "epoch" in meta:
                # skip_batches counts SOURCE records (bad skipped records
                # included — checkpoint meta records_in_epoch, from the
                # prefetcher's source_pos), while step_in_epoch counts
                # TRAINED steps; with --max_bad_records the two diverge
                # and conflating them would replay or drop batches
                return {"path": path, "start_epoch": int(meta["epoch"]),
                        "skip_batches": int(meta.get(
                            "records_in_epoch", meta["step_in_epoch"])),
                        "step_in_epoch": int(meta["step_in_epoch"]),
                        "global_step": int(meta["global_step"]),
                        "meta": meta, "mid_epoch": True}
            epoch = int(meta.get("epoch", manifest.get("step", 0)))
            gs = meta.get("global_step")
            return {"path": path, "start_epoch": epoch + 1,
                    "skip_batches": 0, "step_in_epoch": 0,
                    "global_step": (int(gs) if gs is not None
                                    else (epoch + 1) * steps_per_epoch),
                    "meta": meta, "mid_epoch": False}
    if explicit:
        path, start_epoch = resolve_resume(explicit, args.models_dir,
                                           args.start_epoch)
        return {"path": path, "start_epoch": start_epoch,
                "skip_batches": 0, "step_in_epoch": 0,
                "global_step": start_epoch * steps_per_epoch,
                "meta": {}, "mid_epoch": False}
    return None


def make_supervisor(args, metrics, name: str, save_state):
    """The fault-tolerance supervisor for a training CLI, signal handlers
    installed (docs/RESILIENCE.md). ``save_state(path) -> path`` is the
    CLI's full-train-state writer closure."""
    from dalle_pytorch_tpu.resilience import TrainSupervisor
    return TrainSupervisor(
        name=name, models_dir=args.models_dir, save_state=save_state,
        metrics=metrics, save_every=args.save_every,
        keep=args.keep_checkpoints, spike_factor=args.spike_factor,
        spike_window=args.spike_window, max_rollbacks=args.max_rollbacks,
        rewarm_steps=args.rewarm_steps).install_signal_handlers()


def restore_rollback(sup, optimizer, mesh, param_specs=None):
    """Restore (params, opt_state, ema) from the supervisor's newest valid
    anchor after a NaN/loss-spike verdict. The train step donated the
    now-poisoned buffers, so everything re-enters through the same
    restore + setup_sharded path as a cold resume — including the SAME
    ``param_specs`` the run was set up with (a --pp run re-placed without
    its stage sharding would replicate the full stack on every device);
    the EMA follows the params' placement leaf-by-leaf (make_ema's
    rule)."""
    from dalle_pytorch_tpu.parallel.train import setup_sharded
    path = sup.rollback_target()
    params, opt_state, _ = ckpt.restore_train(path, optimizer)
    params, opt_state = setup_sharded(params, optimizer, mesh,
                                      param_specs=param_specs,
                                      opt_state=opt_state)
    ema = ckpt.restore_ema(path)
    if ema is not None:
        import jax
        ema = jax.tree.map(
            lambda e, p: jax.device_put(e, getattr(p, "sharding", None)),
            ema, params)
    return params, opt_state, ema


def add_common_args(parser: argparse.ArgumentParser,
                    default_batch: int = 24) -> None:
    parser.add_argument("--batchSize", type=int, default=default_batch,
                        help=f"global batch size (default: {default_batch})")
    parser.add_argument("--n_epochs", type=int, default=500,
                        help="number of epochs (default: 500)")
    parser.add_argument("--lr", type=float, default=1e-4,
                        help="learning rate (default: 1e-4)")
    parser.add_argument("--name", type=str, default=None,
                        help="experiment name")
    parser.add_argument("--start_epoch", type=int, default=0,
                        help="start epoch numbering when resuming")
    parser.add_argument("--models_dir", type=str, default="./models",
                        help="checkpoint directory (default: ./models)")
    parser.add_argument("--results_dir", type=str, default="./results",
                        help="sample/recon image directory")
    parser.add_argument("--log_interval", type=int, default=10)
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--dp", type=int, default=0,
                        help="data-parallel devices (0 = all available)")
    parser.add_argument("--profile_dir", type=str, default="",
                        help="capture a jax.profiler trace here")
    parser.add_argument("--coordinator", type=str, default="",
                        help="multi-host: coordinator address host:port "
                             "(or set JAX_COORDINATOR_ADDRESS); on TPU pods "
                             "autodetected")
    parser.add_argument("--num_processes", type=int, default=0,
                        help="multi-host: total process count")
    parser.add_argument("--process_id", type=int, default=-1,
                        help="multi-host: this process's id")
    parser.add_argument("--nan_checks", action="store_true",
                        help="enable jax NaN/Inf trapping (slow)")
    parser.add_argument("--metrics", type=str, default="",
                        help="JSONL metrics file path")
    parser.add_argument("--lr_schedule", default="constant",
                        choices=["constant", "cosine"],
                        help="learning-rate schedule (the reference trains "
                             "at fixed-LR Adam only); 'cosine' decays from "
                             "--lr to --lr*--lr_end_ratio over the "
                             "requested run")
    parser.add_argument("--warmup_steps", type=int, default=0,
                        help="linear LR warmup from 0 over this many steps")
    parser.add_argument("--decay_steps", type=int, default=0,
                        help="cosine decay horizon in steps (0 = the full "
                             "requested run: n_epochs x steps/epoch)")
    parser.add_argument("--lr_end_ratio", type=float, default=0.1,
                        help="cosine floor as a fraction of --lr")
    parser.add_argument("--ema_decay", type=float, default=0.0,
                        help="keep an exponential moving average of the "
                             "params at this decay (e.g. 0.999; 0 = off), "
                             "saved alongside each checkpoint; sample from "
                             "it with gen_dalle --use_ema. Resuming a "
                             "checkpoint that carries an EMA requires the "
                             "flag again (pass -1 to discard the EMA on "
                             "purpose). The reference has no EMA")
    parser.add_argument("--clip_grad_norm", type=float, default=0.0,
                        help="clip gradients to this global L2 norm before "
                             "the optimizer update (0 = off); complements "
                             "the reference's post-update WEIGHT clamp "
                             "(trainVAE.py --clip), which train_vae also "
                             "keeps. Changes the optimizer-state shape: "
                             "pass the same value when resuming a "
                             "checkpoint")
    # -- fault-tolerance runtime (docs/RESILIENCE.md) ----------------------
    parser.add_argument("--auto_resume", action="store_true",
                        help="resume from the newest VALID checkpoint "
                             "(mid-epoch step checkpoints included) before "
                             "falling back to a fresh start; the stream "
                             "continues with zero duplicated or skipped "
                             "steps. --n_epochs still means 'epochs to run "
                             "from the resume point' (the repo-wide resume "
                             "semantic), so pass the REMAINING count — and "
                             "cosine users should pin --decay_steps, since "
                             "the default horizon is recomputed from the "
                             "resume epoch")
    parser.add_argument("--save_every", type=int, default=0,
                        help="write a mid-epoch checkpoint every N steps "
                             "(0 = per-epoch only); these are the anchors "
                             "preemption resume and loss-spike rollback "
                             "restore from")
    parser.add_argument("--keep_checkpoints", type=int, default=3,
                        help="retain this many step checkpoints (older "
                             "ones are GC'd; per-epoch checkpoints are "
                             "never touched)")
    parser.add_argument("--spike_factor", type=float, default=0.0,
                        help="roll back to the last good checkpoint when "
                             "the loss exceeds this multiple of the "
                             "recent-window median (0 = NaN/Inf detection "
                             "only)")
    parser.add_argument("--spike_window", type=int, default=16,
                        help="running-median window for --spike_factor")
    parser.add_argument("--max_rollbacks", type=int, default=2,
                        help="abort (TrainingDiverged) after this many "
                             "loss-spike/NaN rollbacks — repeated spikes "
                             "are divergence, not glitches")
    parser.add_argument("--rewarm_steps", type=int, default=0,
                        help="after a rollback, ramp the LR back up "
                             "linearly over this many steps (0 = resume "
                             "at full LR)")
    parser.add_argument("--max_bad_records", type=int, default=0,
                        help="skip up to this many unreadable/corrupt data "
                             "records per epoch (counted + logged) before "
                             "failing the run")
    parser.add_argument("--init_deadline_s", type=float, default=0.0,
                        help="bound multi-host backend bring-up to this "
                             "many seconds per attempt, with backoff+"
                             "jitter retries (0 = unbounded legacy join)")
    parser.add_argument("--init_retries", type=int, default=3,
                        help="bring-up attempts under --init_deadline_s "
                             "before surfacing a structured failure")
    parser.add_argument("--guard_transfers", action="store_true",
                        help="wrap every train-step body in analysis."
                             "guards.no_transfers(): an implicit host<->"
                             "device transfer in the hot path raises at "
                             "the offending call instead of silently "
                             "stalling the chip each step (explicit "
                             "device_put/device_get still pass). The CI "
                             "train smoke runs with this on — the same "
                             "transfer discipline the serve engine is "
                             "pinned to (docs/STATIC_ANALYSIS.md)")


def step_rng(key, step: int, mesh):
    """``fold_in(key, step)`` with every crossing EXPLICIT: the step
    counter shipped host->device, and the derived key placed replicated
    over ``mesh`` — the jitted step runs on the whole mesh, and a key
    left on the default device alone would be moved there by an implicit
    device-to-device transfer. Value-identical to ``fold_in(key, step)``
    on a python int (fold_in folds the uint32 of the operand either
    way); eager fold_in on an int, like the implicit move, is the one
    thing ``--guard_transfers`` exists to catch, so the per-step RNG
    derivation spells its transfers at the site, like every other
    crossing in the guarded step body."""
    return replicate(mesh, jax.random.fold_in(
        key, jax.device_put(np.uint32(step))))


def resolve_schedule(args, steps_per_epoch: int = 0, start_epoch: int = 0,
                     resume_meta: Optional[dict] = None) -> dict:
    """The LR schedule actually in effect, as a JSON-safe snapshot the
    CLIs persist in every checkpoint's ``meta['lr_schedule']``.

    The cosine horizon resolves in priority order: an explicit
    ``--decay_steps`` > the snapshot persisted in the checkpoint being
    resumed (so an ``--auto_resume`` restart reconstructs the ORIGINAL
    run's schedule without the user re-passing ``--decay_steps`` or
    remembering the original ``--n_epochs``) > the default whole-run
    horizon ``(start_epoch + n_epochs) * steps_per_epoch``."""
    snap = (resume_meta or {}).get("lr_schedule") or {}
    decay = 0
    if args.lr_schedule == "cosine":
        decay = args.decay_steps or int(snap.get("decay_steps") or 0) \
            or max((start_epoch + args.n_epochs) * steps_per_epoch
                   - args.warmup_steps, 1)
        if snap.get("decay_steps") and args.decay_steps \
                and int(snap["decay_steps"]) != args.decay_steps:
            say(f"warning: --decay_steps {args.decay_steps} overrides the "
                f"resumed run's horizon ({snap['decay_steps']} steps)")
    return {"schedule": args.lr_schedule, "lr": args.lr,
            "warmup_steps": args.warmup_steps, "decay_steps": decay,
            "lr_end_ratio": args.lr_end_ratio,
            # the run's total horizon in epochs, for operators reading the
            # manifest (n_epochs is relative to the resume point)
            "epochs_total": int(snap.get("epochs_total")
                                or (start_epoch + args.n_epochs))}


def make_optimizer(args, steps_per_epoch: int = 0, start_epoch: int = 0,
                   schedule: Optional[dict] = None):
    """optax.adam under the requested LR schedule (add_common_args flags).

    The schedule rides the optimizer's step count, which is part of the
    checkpointed opt state — a resumed run continues the schedule where it
    left off, provided the same flags are passed. The cosine horizon comes
    from ``schedule`` (a ``resolve_schedule`` snapshot — pass the one built
    against the resume meta so --auto_resume reconstructs the original
    horizon); without one it is resolved here from the flags alone, where
    the default covers the WHOLE run including already-completed epochs
    (``(start_epoch + n_epochs) * steps_per_epoch``), so callers must
    resolve the resume epoch before building the optimizer.
    ``--clip_grad_norm`` chains a global-norm clip before adam. The
    reference has no equivalent of either (fixed-LR unclipped Adam:
    trainVAE.py:69, trainDALLE.py:166)."""
    import optax
    if schedule is None:
        schedule = resolve_schedule(args, steps_per_epoch, start_epoch)
    if args.lr_schedule == "constant" and not args.warmup_steps:
        sched = args.lr
    elif args.lr_schedule == "constant":
        sched = optax.linear_schedule(0.0, args.lr, args.warmup_steps)
    else:
        sched = optax.warmup_cosine_decay_schedule(
            init_value=0.0, peak_value=args.lr,
            warmup_steps=args.warmup_steps,
            decay_steps=args.warmup_steps + schedule["decay_steps"],
            end_value=args.lr * args.lr_end_ratio)
    clip = getattr(args, "clip_grad_norm", 0.0)
    if clip and clip > 0:
        return optax.chain(optax.clip_by_global_norm(clip),
                           optax.adam(sched))
    return optax.adam(sched)


def make_ema(args, params, resume_path: str = ""):
    """(ema_tree | None, jit update fn | None) for ``--ema_decay``.

    The accumulator is float32 regardless of param dtype: at decay 0.999
    a bfloat16 EMA cannot move (eps ~ 0.008 swallows the (1-d) step).
    On resume the checkpointed EMA continues; a pre-EMA checkpoint falls
    back to the current params as the starting average."""
    if getattr(args, "ema_decay", 0.0) <= 0:
        # resuming a checkpoint THAT HAS an EMA without --ema_decay would
        # silently drop it: the next save writes no ema.msgpack and the
        # accumulated average is gone for good. Refuse; discarding must be
        # explicit (--ema_decay -1).
        if resume_path and os.path.exists(
                os.path.join(resume_path, ckpt.EMA)):
            if getattr(args, "ema_decay", 0.0) < 0:
                say(f"warning: discarding the EMA in {resume_path!r} "
                    "(--ema_decay < 0)")
            else:
                raise SystemExit(
                    f"checkpoint {resume_path!r} carries an EMA but "
                    "--ema_decay was not given — resuming would silently "
                    "drop the accumulated average. Pass the original "
                    "--ema_decay to continue it, or --ema_decay -1 to "
                    "discard it on purpose.")
        return None, None
    import jax
    import jax.numpy as jnp

    ema = ckpt.restore_ema(resume_path) if resume_path else None
    if resume_path:
        # a changed decay on resume is legal (e.g. tightening late in the
        # run) but must not pass silently — the average's horizon changes
        try:
            prev = ckpt.load_manifest(resume_path).get(
                "meta", {}).get("ema_decay")
        except Exception:
            prev = None
        if prev is not None and abs(prev - args.ema_decay) > 1e-12:
            say(f"warning: resume checkpoint was written with --ema_decay "
                f"{prev}; continuing with {args.ema_decay}")
    if ema is None:
        # copy=True: a same-dtype astype would ALIAS the param buffers,
        # which the donating train step deletes on its next call
        ema = jax.tree.map(
            lambda p: jnp.array(p, dtype=jnp.float32, copy=True), params)
    else:
        # follow the params' placement leaf-by-leaf: under a multi-host or
        # stage-sharded (--pp) mesh a bare device_put would leave the EMA
        # host-local while the params are global
        ema = jax.tree.map(
            lambda e, p: jax.device_put(e, getattr(p, "sharding", None)),
            ema, params)
    d = args.ema_decay

    # donate the old EMA: it is dead after `ema = update(ema, params)`,
    # and without donation every step transiently holds two f32 copies
    from dalle_pytorch_tpu.parallel._compat import donate_if_accelerator
    donate = donate_if_accelerator(0)

    @functools.partial(jax.jit, donate_argnums=donate)
    def update(e, p):
        return jax.tree.map(
            lambda a, b: d * a + (1.0 - d) * b.astype(jnp.float32), e, p)

    return ema, update


def ema_as(ema, params):
    """Cast an f32 EMA tree to the dtypes of ``params`` for eval/decode."""
    import jax
    return jax.tree.map(lambda e, p: e.astype(p.dtype), ema, params)


class LoopState:
    """Mutable loop position the training CLIs and their ``save_state``
    closures share with ``run_supervised_loop``. The driver advances it;
    a CLI's checkpoint writer reads it live (mid-epoch saves need the
    exact position, docs/RESILIENCE.md)."""

    def __init__(self, epoch: int = 0, global_step: int = 0):
        self.epoch = epoch
        self.global_step = global_step
        self.epoch_i = 0          # TRAINED steps completed in current epoch
        self.train_loss = 0.0     # epoch-summary accumulators
        self.n_batches = 0
        self.rec_base = 0         # SOURCE records consumed before this
        self.pf = None            # epoch's prefetcher was built
        self.last = None          # payload of the last GOOD step

    @property
    def records_in_epoch(self) -> int:
        """SOURCE records consumed this epoch (bad skipped records
        included) — what checkpoint meta persists for mid-epoch resume;
        diverges from ``epoch_i`` under --max_bad_records."""
        return self.rec_base + (self.pf.source_pos
                                if self.pf is not None else 0)


def run_supervised_loop(args, *, sup, metrics, profiler, dataset, plan,
                        state: LoopState, train_step, on_rollback,
                        on_epoch_end, transform=None, units_of=None,
                        unit_name: str = "tokens", avg_fmt: str = ".4f"):
    """The supervised epoch loop all three training CLIs share: mid-epoch
    skip + accumulator restore, prefetched iteration, the supervisor's
    per-step protocol (fault hooks / NaN-spike rollback / cadence and
    preemption checkpoints), metrics, epoch summaries, and the clean
    ``Preempted`` exit. The CLIs keep what actually differs — batch
    assembly and the epoch tail — as callbacks:

      * ``train_step(item, state) -> (loss_like, payload)`` builds the
        sharded batch (routing it through ``sup.pre_step``), runs the jit
        step (rebinding its params/opt-state closure cells), and returns
        the step loss plus a payload the epoch tail may want (kept on
        ``state.last``, good steps only);
      * ``on_rollback(state)`` restores params/opt state/EMA from the
        supervisor's newest valid anchor (``restore_rollback``);
      * ``on_epoch_end(state, avg) -> checkpoint path`` runs the epoch
        tail (temperature schedule, recon grid / sample, the epoch save)
        and returns the written checkpoint for anchor registration;
      * ``units_of(item)`` sizes the throughput counter; ``transform``
        feeds ``data.prefetch`` (host-side decode off the iterator
        thread).

    The driver owns ``state``; resume exactness (zero duplicated or
    skipped steps) holds exactly as before the extraction —
    tests/test_faults.py pins it end-to-end."""
    from dalle_pytorch_tpu.data import prefetch
    from dalle_pytorch_tpu.resilience import Preempted

    guard_transfers = getattr(args, "guard_transfers", False)
    if guard_transfers:
        from dalle_pytorch_tpu.analysis import guards

    start_epoch = state.epoch
    skip0 = plan["skip_batches"] if plan else 0
    mid_meta = plan["meta"] if (plan and plan["mid_epoch"]) else {}
    try:
        for epoch in range(start_epoch, start_epoch + args.n_epochs):
            state.epoch = epoch
            skip = skip0 if epoch == start_epoch else 0
            # a mid-epoch resume restores the interrupted epoch's summary
            # accumulators so avg_loss covers every step exactly once
            state.train_loss = float(mid_meta.get("train_loss", 0.0)) \
                if skip else 0.0
            state.n_batches = int(mid_meta.get("n_batches", 0)) \
                if skip else 0
            # epoch_i counts TRAINED steps; skip counts SOURCE records
            state.epoch_i = int(mid_meta.get("step_in_epoch", skip)) \
                if skip else 0
            state.rec_base, state.pf = skip, None
            it = dataset.epoch(epoch)
            if skip:
                # deterministic per-epoch order (seeded stateless
                # shuffle): skipping the completed prefix replays nothing
                it = itertools.islice(it, skip, None)
            state.pf = prefetch(it, depth=2, transform=transform,
                                max_bad_records=args.max_bad_records,
                                on_event=lambda r: metrics.event(**r))
            # the loop's phases on the profiler's clock (--profile_dir):
            # inactive annotations cost an atomic read each; no metric of
            # the benchmark reads them, its train cell drives its own loop
            span = jax.profiler.TraceAnnotation
            batches, done = iter(state.pf), object()
            while True:
                with span("trainer.next_batch"):    # the wait on prefetch
                    item = next(batches, done)
                if item is done:
                    break
                gs = state.global_step
                with jax.profiler.StepTraceAnnotation("train", step_num=gs):
                    profiler.maybe_start(gs)
                    if guard_transfers:
                        # the ROADMAP's no_transfers-around-the-train-step
                        # item: the step body must spell every host<->device
                        # crossing as an explicit device_put at the site
                        # (shard_batch, step_rng, the CLIs' batch loaders) —
                        # an implicit one raises HERE, naming the call,
                        # instead of stalling the chip silently every step.
                        # The loss fetch (float(loss) below) stays OUTSIDE
                        # the guard: it is the loop's one intentional
                        # per-step host read
                        with guards.no_transfers(), span("trainer.step"):
                            loss, payload = train_step(item, state)
                    else:
                        with span("trainer.step"):
                            loss, payload = train_step(item, state)
                    with span("trainer.fetch_loss"):
                        lv = float(loss)
                    # after the fetch, so that a capture holds whole steps:
                    # the device has then finished the last one it covers
                    profiler.maybe_stop(gs)
                    with span("trainer.supervise"):
                        if sup.check_step(gs, lv) == sup.ROLLBACK:
                            on_rollback(state)
                            state.global_step += 1
                            state.epoch_i += 1
                            continue
                        metrics.step(gs, lv, epoch=epoch,
                                     units=units_of(item) if units_of else 0,
                                     unit_name=unit_name)
                        state.train_loss += lv
                        state.n_batches += 1
                        state.global_step += 1
                        state.epoch_i += 1
                        state.last = payload
                        sup.end_step(state.global_step)
            if state.n_batches == 0:
                raise RuntimeError("empty dataset epoch")

            avg = state.train_loss / state.n_batches
            say(f"====> Epoch: {epoch} Average loss: {avg:{avg_fmt}}")
            state.epoch_i = 0  # epoch complete: saved meta must say so
            path = on_epoch_end(state, avg)
            if path:
                sup.register_checkpoint(path)
            mid_meta = {}
            skip0 = 0
    except Preempted as p:
        say(f"preempted — state saved to {p.path}; restart with "
            "--auto_resume to continue")
        return
    finally:
        sup.close()
        profiler.close()


def load_caption_dataset(args):
    """(vocab, host-sharded CaptionDataset) from the --captions* flags —
    the reference's caption data contract (SURVEY.md §5), shared by
    train_dalle and train_clip. Saves the vocab next to the checkpoints
    (process 0 only on shared filesystems)."""
    from dalle_pytorch_tpu.data import (CaptionDataset, load_caption_data,
                                        shard_for_host)
    from dalle_pytorch_tpu.parallel.multihost import is_primary
    vocab, data = load_caption_data(args.captions_only, args.captions,
                                    args.text_seq_len)
    if is_primary():
        vocab.save(os.path.join(args.models_dir, f"{args.name}-vocab.json"))
    data = list(shard_for_host(data))
    say(f"{len(data)} caption/image pairs on this host")
    return vocab, CaptionDataset(data, batch_size=args.batchSize,
                                 shuffle=True, seed=args.seed)


def setup_run(args, unit_name: str = "tokens"):
    """-> (mesh, MetricsLogger, StepProfiler). Applies NaN toggles/seeding,
    places the compile cache (utils.device) and logs the device once.

    Joins the multi-host cluster first when configured (flags or env —
    parallel.multihost), so the mesh below spans every host's devices.
    With --init_deadline_s the join is deadline-bounded and retried with
    backoff+jitter; exhausted retries exit with the structured bring-up
    failure record instead of hanging (resilience.retry)."""
    from dalle_pytorch_tpu.parallel.multihost import initialize
    from dalle_pytorch_tpu.resilience import BringupError, faults
    from dalle_pytorch_tpu.utils.device import (describe_device,
                                                enable_compile_cache)
    enable_compile_cache()
    faults.maybe_activate_from_env()
    try:
        initialize(coordinator_address=args.coordinator or None,
                   num_processes=args.num_processes or None,
                   process_id=args.process_id if args.process_id >= 0
                   else None,
                   deadline_s=args.init_deadline_s or None,
                   max_attempts=args.init_retries,
                   on_event=lambda rec: say(f"[resilience] {rec}"))
    except BringupError as e:
        import json as _json
        raise SystemExit(
            "backend bring-up failed: " + _json.dumps(e.record)) from e
    # the device this run actually got, once, first thing in every log:
    # a jax that fell back to the CPU must not pass for a chip run
    say(f"device: {describe_device()}")
    if args.nan_checks:
        enable_nan_checks(True)
    np.random.seed(args.seed)
    n = args.dp or len(jax.devices())
    if jax.process_count() > 1 and n != len(jax.devices()):
        # every process must own devices in the mesh and join the same
        # computation — a --dp subset would exclude some hosts' chips and
        # deadlock at the first collective
        raise SystemExit(
            f"--dp {args.dp} is not supported in multi-host mode: the mesh "
            f"must span all {len(jax.devices())} global devices")
    sp = getattr(args, "sp", 0) or 1
    pp = getattr(args, "pp", 0) or 1
    if sp > 1 and pp > 1:
        raise SystemExit("--sp and --pp cannot be combined (pick one "
                         "model-parallel axis per run)")
    if sp > 1 and n % sp:
        raise SystemExit(f"--sp {sp} must divide the device count ({n})")
    if pp > 1 and n % pp:
        raise SystemExit(f"--pp {pp} must divide the device count ({n})")
    if sp > 1:
        axes = {"dp": n // sp, "sp": sp}
    elif pp > 1:
        axes = {"dp": n // pp, "pp": pp}
    else:
        axes = {"dp": n}
    mesh = make_mesh(axes, jax.devices()[:n])
    # the train loops feed MetricsLogger host-LOCAL units, so the per-chip
    # denominator is this host's share of the mesh
    metrics = MetricsLogger(args.metrics or None,
                            log_interval=args.log_interval,
                            n_devices=n // jax.process_count())
    profiler = StepProfiler(args.profile_dir or None)
    os.makedirs(args.models_dir, exist_ok=True)
    os.makedirs(args.results_dir, exist_ok=True)
    return mesh, metrics, profiler

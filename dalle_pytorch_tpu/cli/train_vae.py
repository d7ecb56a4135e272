"""DiscreteVAE training CLI — the reference trainVAE.py, TPU-native.

Capability parity (reference trainVAE.py:1-119): argparse flags with the
same names, Adam, loss = smooth_l1 + mse (reference :87), optional per-epoch
temperature decay ``0.7 ** (1/len(loader))`` (reference :78,104-105),
optional per-step weight clamping (reference :71-74,95-96), per-epoch
[input | recon | decode(argmax codes)] grids (reference :109-114), and a
per-epoch checkpoint under ``{models_dir}/{name}-{epoch}`` (reference :119,
the cross-CLI contract train_dalle/gen_dalle/mix_vae read).

TPU-first differences:
  * ONE jit-compiled train step (loss+grads+adam+clamp fused by XLA) over a
    ``dp`` mesh — batch sharded, gradient psum over ICI; the temperature is
    a traced scalar input so the schedule never recompiles;
  * host image loading is prefetched on a background thread while the chip
    runs the current step (data.prefetch);
  * checkpoints carry optimizer state + config, so resume is exact
    (improvement over the reference's weights-only .pth).

Run: python -m dalle_pytorch_tpu.cli.train_vae --dataPath ./imagedata
"""

from __future__ import annotations

import argparse
import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
import optax

from dalle_pytorch_tpu import checkpoint as ckpt
from dalle_pytorch_tpu.cli.common import (LoopState, add_common_args,
                                          make_optimizer, make_supervisor,
                                          plan_resume, resolve_schedule,
                                          restore_rollback,
                                          run_supervised_loop, say,
                                          setup_run, step_rng)
from dalle_pytorch_tpu.data import ImageFolderDataset, save_image_grid, \
    shard_for_host
from dalle_pytorch_tpu.models import vae as V
from dalle_pytorch_tpu.parallel import replicate, shard_batch
from dalle_pytorch_tpu.parallel.train import setup_sharded


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        description="train DiscreteVAE (TPU-native DALLE-pytorch)")
    add_common_args(p, default_batch=24)
    p.add_argument("--dataPath", type=str, default="./imagedata",
                   help="path to image folder (default: ./imagedata)")
    p.add_argument("--imageSize", type=int, default=256)
    p.add_argument("--tempsched", action="store_true", default=False,
                   help="use temperature scheduling")
    p.add_argument("--temperature", type=float, default=0.9)
    p.add_argument("--loadVAE", type=str, default="",
                   help="checkpoint path (or name with --start_epoch) to "
                        "continue training")
    p.add_argument("--clip", type=float, default=0,
                   help="clamp weights to [-clip, clip], 0 = off")
    # model hyperparams (reference trainVAE.py:42-50 hardcodes these)
    p.add_argument("--num_layers", type=int, default=3)
    p.add_argument("--num_tokens", type=int, default=2048)
    p.add_argument("--codebook_dim", type=int, default=256)
    p.add_argument("--hidden_dim", type=int, default=128)
    p.add_argument("--num_resnet_blocks", type=int, default=0)
    p.add_argument("--straight_through", action="store_true")
    p.add_argument("--grad_accum", type=int, default=1,
                   help="accumulate gradients over this many microbatches "
                        "per optimizer step (batchSize must divide)")
    p.add_argument("--param_dtype", default="float32",
                   choices=["float32", "bfloat16"],
                   help="dtype for NEW runs' params (bfloat16 halves HBM "
                        "and keeps every matmul on the MXU's native "
                        "precision; resumed runs keep the checkpoint's "
                        "dtype)")
    p.set_defaults(name="vae")
    return p


def make_step(cfg: V.VAEConfig, optimizer, clip: float,
              grad_accum: int = 1):
    """jit step: (params, opt_state, batch{'images','temperature'}, rng) ->
    (params, opt_state, loss). Loss = smooth_l1 + mse (reference
    trainVAE.py:87); the optional weight clamp runs inside the same compiled
    step (reference clampWeights applies per step, :71-74,95-96)."""

    def loss_fn(params, batch, rng):
        imgs = batch["images"]
        recon = V.vae_apply(params, imgs, cfg=cfg, rng=rng,
                            temperature=batch["temperature"])
        d = jnp.abs(imgs - recon)
        huber = jnp.mean(jnp.where(d < 1.0, 0.5 * d * d, d - 0.5))
        return huber + jnp.mean(jnp.square(imgs - recon))

    from dalle_pytorch_tpu.parallel._compat import donate_if_accelerator
    donate = donate_if_accelerator(0, 1)

    @functools.partial(jax.jit, donate_argnums=donate)
    def step(params, opt_state, batch, rng):
        batch = dict(batch)
        # optional traced update scale (resilience LR re-warm) — for Adam
        # exactly an LR multiplier, like parallel.train.make_train_step
        lr_scale = batch.pop("lr_scale", None)
        if grad_accum > 1:
            from dalle_pytorch_tpu.parallel.train import accumulate_grads
            loss, grads = accumulate_grads(loss_fn, params, batch, rng,
                                           grad_accum)
        else:
            loss, grads = jax.value_and_grad(loss_fn)(params, batch, rng)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        if lr_scale is not None:
            updates = jax.tree.map(
                lambda u: (u * lr_scale).astype(u.dtype), updates)
        params = optax.apply_updates(params, updates)
        if clip > 0:
            params = jax.tree.map(lambda p: jnp.clip(p, -clip, clip), params)
        return params, opt_state, loss

    return step


def main(argv=None):
    args = build_parser().parse_args(argv)
    mesh, metrics, profiler = setup_run(args, unit_name="images")

    cfg = V.VAEConfig(
        image_size=args.imageSize, num_tokens=args.num_tokens,
        codebook_dim=args.codebook_dim, num_layers=args.num_layers,
        num_resnet_blocks=args.num_resnet_blocks,
        hidden_dim=args.hidden_dim, temperature=args.temperature,
        straight_through=args.straight_through)

    dataset = ImageFolderDataset(args.dataPath, args.imageSize,
                                 args.batchSize, shuffle=True,
                                 seed=args.seed)
    # multi-host: each process reads its slice of the files
    dataset.files = list(shard_for_host(dataset.files))

    key = jax.random.PRNGKey(args.seed)

    temperature = args.temperature
    # resolve the resume point BEFORE building the optimizer: the cosine
    # horizon must cover already-completed epochs too. --auto_resume picks
    # the newest VALID checkpoint (mid-epoch step checkpoints included),
    # whose persisted schedule snapshot reconstructs the original horizon.
    plan = plan_resume(args, args.name, explicit=args.loadVAE,
                       steps_per_epoch=len(dataset))
    start_epoch = plan["start_epoch"] if plan else args.start_epoch
    resume_path = plan["path"] if plan else None
    sched = resolve_schedule(args, steps_per_epoch=len(dataset),
                             start_epoch=start_epoch,
                             resume_meta=plan["meta"] if plan else None)
    optimizer = make_optimizer(args, schedule=sched)
    opt_state = None
    if resume_path:
        params, opt_state, manifest = ckpt.restore_train(resume_path,
                                                         optimizer)
        cfg = ckpt.vae_config_from_manifest(manifest)
        temperature = manifest["meta"].get("temperature", temperature)
        say(f"resumed VAE from {resume_path}")
        if plan["mid_epoch"]:
            metrics.resilience("resume", checkpoint=resume_path,
                               epoch=start_epoch,
                               step_in_epoch=plan["step_in_epoch"],
                               records_in_epoch=plan["skip_batches"],
                               global_step=plan["global_step"])
    else:
        params = V.vae_init(key, cfg, dtype=jnp.dtype(args.param_dtype))

    params, opt_state = setup_sharded(params, optimizer, mesh,
                                      opt_state=opt_state)
    step = make_step(cfg, optimizer, args.clip,
                     grad_accum=args.grad_accum)
    from dalle_pytorch_tpu.cli.common import make_ema
    ema, ema_update = make_ema(args, params, resume_path or "")

    dk = 0.7 ** (1.0 / max(len(dataset), 1))
    if args.tempsched:
        say("Scale Factor:", dk)

    @jax.jit
    def eval_fn(params, images, rng, temperature):
        """[gumbel recon | argmax-token decode] for the per-epoch grid
        (reference trainVAE.py:109-114)."""
        recon = V.vae_apply(params, images, cfg=cfg, rng=rng,
                            temperature=temperature)
        decoded = V.decode(params, V.get_codebook_indices(params, images))
        return recon, decoded

    # mutable loop state the supervisor's save_state closure reads live
    # (run_supervised_loop advances it)
    state = LoopState(epoch=start_epoch,
                      global_step=plan["global_step"] if plan else 0)

    def save_state(path):
        """Full mid-epoch train state — resume needs params, opt state,
        EMA, schedule meta AND the loop position (global_step/epoch/
        step_in_epoch + accumulators for the epoch summary)."""
        return ckpt.save(
            path, params, step=state.global_step, config=cfg,
            opt_state=opt_state, kind="vae",
            meta={"temperature": temperature, "epoch": state.epoch,
                  "step_in_epoch": state.epoch_i,
                  "global_step": state.global_step,
                  "records_in_epoch": state.records_in_epoch,
                  "train_loss": state.train_loss,
                  "n_batches": state.n_batches, "lr_schedule": sched,
                  **({"ema_decay": args.ema_decay} if ema is not None
                     else {})}, ema=ema)

    sup = make_supervisor(args, metrics, args.name, save_state)
    if resume_path:
        # the checkpoint we just restored from is a valid rollback
        # anchor — without it a NaN before the first cadence/epoch
        # save after resume would raise instead of rolling back
        sup.register_checkpoint(resume_path)

    def train_step(images, state):
        nonlocal params, opt_state, ema
        # every crossing is explicit and lands on the MESH (shard_batch's
        # device_put, the replicated temperature scalar, step_rng) so
        # the body runs clean under --guard_transfers at any dp
        batch = shard_batch(mesh, {"images": images})
        batch["temperature"] = replicate(mesh, np.float32(temperature))
        batch = sup.pre_step(state.global_step, batch)
        params, opt_state, loss = step(
            params, opt_state, batch,
            step_rng(key, state.global_step, mesh))
        if ema is not None:
            ema = ema_update(ema, params)
        return loss, batch

    def on_rollback(state):
        nonlocal params, opt_state, ema
        params, opt_state, ema = restore_rollback(sup, optimizer, mesh)

    def on_epoch_end(state, avg):
        nonlocal temperature
        epoch = state.epoch
        if args.tempsched:
            temperature *= dk
            say("Current temperature: ", temperature)

        # per-epoch recon grid (input | recon | argmax decode), first 8.
        # fetch_local: the batch is dp-sharded across (possibly) hosts —
        # allgather the k rows so every process feeds the jit identical
        # data (SPMD) and np.asarray never touches non-addressable
        # shards. A resume that landed exactly on the epoch boundary has
        # no batch in hand — skip the grid, keep the checkpoint.
        if state.last is not None:
            from dalle_pytorch_tpu.parallel.multihost import fetch_local
            k = min(8, args.batchSize)
            imgs = jnp.asarray(fetch_local(state.last["images"])[:k])
            recons, decoded = eval_fn(params, imgs,
                                      jax.random.fold_in(key, epoch),
                                      jnp.float32(temperature))
            grid = np.concatenate([np.asarray(imgs), np.asarray(recons),
                                   np.asarray(decoded)])
            grid_path = os.path.join(args.results_dir,
                                     f"{args.name}_epoch_{epoch}.png")
            save_image_grid(grid, grid_path, nrow=k)

        path = ckpt.save(
            ckpt.ckpt_path(args.models_dir, args.name, epoch), params,
            step=epoch, config=cfg, opt_state=opt_state, kind="vae",
            meta={"temperature": temperature, "epoch": epoch,
                  "avg_loss": avg, "global_step": state.global_step,
                  "lr_schedule": sched,
                  **({"ema_decay": args.ema_decay} if ema is not None
                     else {})}, ema=ema)
        metrics.event(event="checkpoint", path=path, epoch=epoch,
                      avg_loss=avg, temperature=temperature)
        return path

    run_supervised_loop(
        args, sup=sup, metrics=metrics, profiler=profiler, dataset=dataset,
        plan=plan, state=state, train_step=train_step,
        on_rollback=on_rollback, on_epoch_end=on_epoch_end,
        units_of=lambda images: images.shape[0], unit_name="images",
        avg_fmt=".8f")


if __name__ == "__main__":
    main()

"""CLIP training CLI — contrastive text/image pretraining for the reranker.

The reference ships the CLIP model and README usage (reference
dalle_pytorch.py:161-237, README.md:90-115) but no training script; this
CLI closes that gap with the same data contract as train_dalle (captions
file + `path : caption` pairs + imagefolder, SURVEY.md §5 data contract)
so one dataset serves the whole pipeline. The trained checkpoint plugs
into ``gen_dalle --clip_name`` for generation reranking (reference
dalle_pytorch.py:354-356).

One jit train step over a ``dp`` mesh; loss is the reference's
one-directional (text→image) InfoNCE with a learned pre-exp temperature.

Run: python -m dalle_pytorch_tpu.cli.train_clip --dataPath ./imagedata
"""

from __future__ import annotations

import argparse
import os

import jax
import jax.numpy as jnp
import numpy as np

from dalle_pytorch_tpu import checkpoint as ckpt
from dalle_pytorch_tpu.cli.common import (LoopState, add_common_args,
                                          load_caption_dataset, make_ema,
                                          make_optimizer, make_supervisor,
                                          plan_resume, resolve_schedule,
                                          restore_rollback,
                                          run_supervised_loop, say,
                                          setup_run, step_rng)
from dalle_pytorch_tpu.data import load_image_batch
from dalle_pytorch_tpu.models import clip as C
from dalle_pytorch_tpu.parallel import make_train_step, shard_batch
from dalle_pytorch_tpu.parallel.train import clip_loss_fn, setup_sharded


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        description="train CLIP (TPU-native DALLE-pytorch)")
    add_common_args(p, default_batch=32)
    p.add_argument("--dataPath", type=str, default="./imagedata")
    p.add_argument("--imageSize", type=int, default=256)
    p.add_argument("--captions_only", type=str,
                   default="od-captionsonly.txt")
    p.add_argument("--captions", type=str, default="od-captions.txt")
    p.add_argument("--load_clip", type=str, default="",
                   help="checkpoint path or name to continue training")
    p.add_argument("--grad_accum", type=int, default=1)
    # model hyperparams (reference CLIP __init__ defaults,
    # dalle_pytorch.py:162-178)
    p.add_argument("--dim_text", type=int, default=512)
    p.add_argument("--dim_image", type=int, default=512)
    p.add_argument("--dim_latent", type=int, default=512)
    p.add_argument("--num_text_tokens", type=int, default=10000)
    p.add_argument("--text_seq_len", type=int, default=256)
    p.add_argument("--text_enc_depth", type=int, default=6)
    p.add_argument("--text_heads", type=int, default=8)
    p.add_argument("--visual_enc_depth", type=int, default=6)
    p.add_argument("--visual_heads", type=int, default=8)
    p.add_argument("--visual_patch_size", type=int, default=32)
    p.add_argument("--dense", action="store_true",
                   help="dense attention (default mirrors the reference "
                        "Transformer default sparse_attn=True)")
    p.add_argument("--param_dtype", default="float32",
                   choices=["float32", "bfloat16"])
    p.set_defaults(name="clip")
    return p


def main(argv=None):
    args = build_parser().parse_args(argv)
    mesh, metrics, profiler = setup_run(args, unit_name="pairs")

    cfg = C.CLIPConfig(
        dim_text=args.dim_text, dim_image=args.dim_image,
        dim_latent=args.dim_latent, num_text_tokens=args.num_text_tokens,
        text_seq_len=args.text_seq_len, text_enc_depth=args.text_enc_depth,
        text_heads=args.text_heads, visual_enc_depth=args.visual_enc_depth,
        visual_heads=args.visual_heads,
        visual_image_size=args.imageSize,
        visual_patch_size=args.visual_patch_size,
        sparse_attn=not args.dense)

    # data first: the cosine schedule's default horizon is the requested
    # run length, n_epochs x steps/epoch
    vocab, dataset = load_caption_dataset(args)

    key = jax.random.PRNGKey(args.seed)

    # resolve the resume point BEFORE building the optimizer: the cosine
    # horizon must cover already-completed epochs too. --auto_resume picks
    # the newest VALID checkpoint (mid-epoch step checkpoints included).
    plan = plan_resume(args, args.name, explicit=args.load_clip,
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
        cfg = C.CLIPConfig(**manifest["config"])
        say(f"resumed CLIP from {resume_path}")
        if plan["mid_epoch"]:
            metrics.resilience("resume", checkpoint=resume_path,
                               epoch=start_epoch,
                               step_in_epoch=plan["step_in_epoch"],
                               records_in_epoch=plan["skip_batches"],
                               global_step=plan["global_step"])
    else:
        params = C.clip_init(key, cfg, dtype=jnp.dtype(args.param_dtype))

    params, opt_state = setup_sharded(params, optimizer, mesh,
                                      opt_state=opt_state)
    step = make_train_step(clip_loss_fn(cfg), optimizer,
                           grad_accum=args.grad_accum)
    ema, ema_update = make_ema(args, params, resume_path or "")

    def load_batch(item):
        paths, toks = item
        images = load_image_batch(paths, args.dataPath, args.imageSize)
        return {"text": toks, "images": images,
                "mask": np.asarray(toks) != 0}          # PAD = 0

    # mutable loop state the supervisor's save_state closure reads live
    # (run_supervised_loop advances it)
    state = LoopState(epoch=start_epoch,
                      global_step=plan["global_step"] if plan else 0)

    def save_state(path):
        return ckpt.save(
            path, params, step=state.global_step, config=cfg,
            opt_state=opt_state, kind="clip",
            meta={"epoch": state.epoch, "step_in_epoch": state.epoch_i,
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

    def train_step(hosted, state):
        nonlocal params, opt_state, ema
        batch = shard_batch(mesh, hosted)
        batch = sup.pre_step(state.global_step, batch)
        params, opt_state, loss = step(
            params, opt_state, batch,
            step_rng(key, state.global_step, mesh))
        if ema is not None:
            ema = ema_update(ema, params)
        return loss, None

    def on_rollback(state):
        nonlocal params, opt_state, ema
        params, opt_state, ema = restore_rollback(sup, optimizer, mesh)

    def on_epoch_end(state, avg):
        epoch = state.epoch
        path = ckpt.save(
            ckpt.ckpt_path(args.models_dir, args.name, epoch), params,
            step=epoch, config=cfg, opt_state=opt_state, kind="clip",
            meta={"epoch": epoch, "avg_loss": avg,
                  "global_step": state.global_step, "lr_schedule": sched,
                  **({"ema_decay": args.ema_decay} if ema is not None
                     else {})}, ema=ema)
        metrics.event(event="checkpoint", path=path, epoch=epoch,
                      avg_loss=avg)
        return path

    run_supervised_loop(
        args, sup=sup, metrics=metrics, profiler=profiler, dataset=dataset,
        plan=plan, state=state, train_step=train_step,
        on_rollback=on_rollback, on_epoch_end=on_epoch_end,
        transform=load_batch, units_of=lambda item: args.batchSize,
        unit_name="pairs")


if __name__ == "__main__":
    main()

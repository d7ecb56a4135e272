"""DALLE training CLI — the reference trainDALLE.py, TPU-native.

Capability parity (reference trainDALLE.py:1-217): loads the pretrained VAE
checkpoint written by train_vae (``{models_dir}/{vaename}-{vae_epoch}``, the
cross-CLI contract, reference :64-67), ties the DALLE image embedding to its
codebook (reference dalle_pytorch.py:283), builds the word vocabulary from
the captions-only corpus (reference :92-111), iterates (image, padded
caption) minibatches with an all-True text mask (reference :135-192), Adam,
per-epoch checkpoint + a generated sample grid from the last minibatch's
captions (reference :212-217).

TPU-first differences:
  * image -> token-id encoding runs as its own jit fn per batch (the frozen
    VAE never enters the train graph — same no-grad semantics as reference
    :375-378, without hauling VAE params into the step executable);
  * ONE jit train step over a ``dp`` mesh (gradient psum over ICI), host
    image reads prefetched on a background thread;
  * the per-epoch sample uses the jit lax.scan KV-cache sampler
    (models.dalle.generate_images) instead of 1024 full re-forwards;
  * checkpoints carry optimizer state + both configs; the vocabulary is
    saved alongside (``{name}-vocab.json``) so gen_dalle can rebuild ids
    without re-reading the corpus.

Run: python -m dalle_pytorch_tpu.cli.train_dalle --dataPath ./imagedata \
        --captions_only od-captionsonly.txt --captions od-captions.txt
"""

from __future__ import annotations

import argparse
import dataclasses
import functools
import os

import jax
import jax.numpy as jnp
import numpy as np

from dalle_pytorch_tpu import checkpoint as ckpt
from dalle_pytorch_tpu.cli.common import (LoopState, add_common_args,
                                          load_caption_dataset,
                                          make_optimizer, make_supervisor,
                                          plan_resume, resolve_schedule,
                                          restore_rollback,
                                          run_supervised_loop, say,
                                          setup_run, step_rng)
from dalle_pytorch_tpu.data import load_image_batch, save_image_grid
from dalle_pytorch_tpu.models import dalle as D
from dalle_pytorch_tpu.models import vae as V
from dalle_pytorch_tpu.parallel import shard_batch
from dalle_pytorch_tpu.parallel.train import make_train_step, setup_sharded


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        description="train DALLE (TPU-native DALLE-pytorch)")
    add_common_args(p, default_batch=24)
    p.add_argument("--dataPath", type=str, default="./imagedata")
    p.add_argument("--imageSize", type=int, default=256)
    p.add_argument("--captions_only", type=str,
                   default="od-captionsonly.txt",
                   help="captions corpus, one per line (builds the vocab)")
    p.add_argument("--captions", type=str, default="od-captions.txt",
                   help="'filename : caption' pairs file")
    p.add_argument("--vaename", type=str, default="vae",
                   help="VAE checkpoint experiment name")
    p.add_argument("--vae_epoch", type=int, default=0,
                   help="VAE checkpoint epoch to load")
    p.add_argument("--load_dalle", type=str, default="",
                   help="DALLE checkpoint (path or name) to continue from")
    p.add_argument("--sample_every", type=int, default=1,
                   help="generate a sample grid every N epochs (0 = never)")
    # model hyperparams (reference trainDALLE.py:70-81 hardcodes these)
    p.add_argument("--dim", type=int, default=256)
    p.add_argument("--depth", type=int, default=6)
    p.add_argument("--heads", type=int, default=8)
    p.add_argument("--dim_head", type=int, default=64)
    p.add_argument("--num_text_tokens", type=int, default=10000)
    p.add_argument("--text_seq_len", type=int, default=256)
    def _prob(v):
        v = float(v)
        if not 0.0 <= v <= 1.0:
            raise argparse.ArgumentTypeError(
                f"must be a probability in [0, 1], got {v}")
        return v

    p.add_argument("--caption_drop", type=_prob, default=0.0,
                   help="per-sample probability of replacing the caption "
                        "with the all-PAD null caption during training — "
                        "enables classifier-free guidance at generation "
                        "time (gen_dalle --guidance); dense path only")
    p.add_argument("--attn_dropout", type=float, default=0.1)
    p.add_argument("--ff_dropout", type=float, default=0.1)
    p.add_argument("--reversible", action="store_true")
    p.add_argument("--sparse_attn", action="store_true",
                   help="alternate sparse/dense attention layers")
    p.add_argument("--attn_impl", type=str, default="xla",
                   choices=["xla", "flash"])
    p.add_argument("--attn_bwd_impl", type=str, default="xla",
                   choices=["xla", "pallas", "pallas_fused"],
                   help="flash backward: XLA blockwise scan, the split "
                        "Pallas dq/dkv kernels (causal tile skipping), or "
                        "the single-pass fused Pallas kernel (one score "
                        "computation per tile pair)")
    p.add_argument("--sparse_impl", type=str, default="windowed",
                   choices=["ref", "windowed", "pallas"],
                   help="'windowed' is the exact fast path (block-diagonal "
                        "+ global strip, ~16x fewer FLOPs at seq 1280)")
    p.add_argument("--moe_experts", type=int, default=0,
                   help="replace every FF with a top-k MoE of this many "
                        "experts (0 = plain GEGLU; beyond-reference)")
    p.add_argument("--moe_k", type=int, default=2)
    p.add_argument("--grad_accum", type=int, default=1,
                   help="accumulate gradients over this many microbatches "
                        "per optimizer step (batchSize must divide)")
    p.add_argument("--sp", type=int, default=0,
                   help="sequence-parallel mesh axis size (devices split "
                        "dp x sp; the token axis shards over sp with ring "
                        "attention; dropout uses per-position keys)")
    p.add_argument("--sp_impl", default="ring", choices=["ring", "ulysses"])
    p.add_argument("--pp", type=int, default=0,
                   help="pipeline-parallel stage count (devices split "
                        "dp x pp; depth/pp consecutive layers per stage, "
                        "GPipe microbatching over ICI)")
    p.add_argument("--pp_microbatches", type=int, default=0,
                   help="microbatches per pipeline step (default = --pp; "
                        "more shrinks the pp-1-tick bubble)")
    p.add_argument("--param_dtype", default="float32",
                   choices=["float32", "bfloat16"],
                   help="dtype for NEW runs' params (resumed runs keep "
                        "the checkpoint's dtype)")
    p.add_argument("--loss_chunk", type=int, default=0,
                   help="stream the CE head over sequence chunks of this "
                        "size (0 = dense); caps logits memory at "
                        "(batch, chunk, vocab)")
    p.add_argument("--remat", default="none",
                   choices=["none", "save_ln", "dots", "full"],
                   help="rematerialize the scanned layer body in backward: "
                        "'save_ln' drops only the f32 layernorm saves "
                        "(cheapest recompute for the bytes that drive OOM), "
                        "'dots' recomputes only vector work (matmul outputs "
                        "stay saved, ~2/3 of activation bytes reclaimed at "
                        "near-zero FLOP cost), 'full' recomputes the whole "
                        "body (~1/3 more FLOPs, near-zero saved "
                        "activations) — the levers that let batches beyond "
                        "16 fit one 16G chip")
    p.set_defaults(name="test")
    return p


def main(argv=None):
    args = build_parser().parse_args(argv)
    if args.caption_drop > 0 and (args.sp > 1 or args.pp > 1):
        raise SystemExit("--caption_drop is supported on the dense path "
                         "only (not --sp/--pp)")
    mesh, metrics, profiler = setup_run(args)

    # -- VAE (frozen tokenizer/decoder) — the cross-CLI contract ----------
    vae_path = ckpt.ckpt_path(args.models_dir, args.vaename, args.vae_epoch)
    say(f"loading VAE from {vae_path}")
    vae_params, vae_manifest = ckpt.restore_params(vae_path)
    vae_cfg = ckpt.vae_config_from_manifest(vae_manifest)

    sparse = (True, False) * (args.depth // 2) if args.sparse_attn else False
    cfg = D.DALLEConfig(
        dim=args.dim, depth=args.depth, vae=vae_cfg,
        num_text_tokens=args.num_text_tokens,
        text_seq_len=args.text_seq_len, heads=args.heads,
        dim_head=args.dim_head, reversible=args.reversible,
        attn_dropout=args.attn_dropout, ff_dropout=args.ff_dropout,
        sparse_attn=sparse, attn_impl=args.attn_impl,
        attn_bwd_impl=args.attn_bwd_impl,
        moe_experts=args.moe_experts, moe_k=args.moe_k,
        sparse_impl=args.sparse_impl, loss_chunk=args.loss_chunk,
        remat=args.remat)

    # data first: the cosine schedule's default horizon is the requested
    # run length, n_epochs x steps/epoch
    vocab, dataset = load_caption_dataset(args)

    key = jax.random.PRNGKey(args.seed)

    # resolve the resume point BEFORE building the optimizer: the cosine
    # horizon must cover already-completed epochs too. --auto_resume picks
    # the newest VALID checkpoint (mid-epoch step checkpoints included).
    ckpt_name = f"{args.name}_dalle"
    explicit = ""
    if args.load_dalle:
        explicit = args.load_dalle if os.path.isdir(args.load_dalle) \
            else f"{args.load_dalle}_dalle"
    plan = plan_resume(args, ckpt_name, explicit=explicit,
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
        cfg = ckpt.dalle_config_from_manifest(manifest)
        # remat is a pure execution/memory knob (no effect on params or
        # numerics — tests/test_transformer.py grad parity), so the CLI
        # value applies on resume too: resuming at a bigger batch with
        # --remat full is exactly the advertised use
        cfg = dataclasses.replace(cfg, remat=args.remat)
        say(f"resumed DALLE from {resume_path}")
        if plan["mid_epoch"]:
            metrics.resilience("resume", checkpoint=resume_path,
                               epoch=start_epoch,
                               step_in_epoch=plan["step_in_epoch"],
                               records_in_epoch=plan["skip_batches"],
                               global_step=plan["global_step"])
    else:
        # ties image_emb to the VAE codebook (reference dalle_pytorch.py:283)
        params = D.dalle_init(key, cfg, vae_params=vae_params,
                              dtype=jnp.dtype(args.param_dtype))

    param_specs = None
    if args.pp and args.pp > 1:
        # stage-shard the transformer stack so each device stores only its
        # depth/pp layer slice (plus the replicated embeddings/head)
        from dalle_pytorch_tpu.parallel import pp_param_specs
        if cfg.depth % args.pp:
            raise SystemExit(f"--pp {args.pp} must divide depth {cfg.depth}")
        param_specs = pp_param_specs(params)
    params, opt_state = setup_sharded(params, optimizer, mesh,
                                      param_specs=param_specs,
                                      opt_state=opt_state)

    # -- data --------------------------------------------------------------
    tokenize = jax.jit(functools.partial(V.get_codebook_indices, vae_params))

    def load_batch(item):
        paths, toks = item
        images = load_image_batch(paths, args.dataPath, args.imageSize)
        return {"text": toks, "images": images}

    if args.sp and args.sp > 1:
        # sequence-parallel training: the token axis shards over the sp
        # mesh axis, ring/Ulysses attention inside one shard_map
        from dalle_pytorch_tpu.parallel import sp_dalle_loss_fn
        loss_fn = sp_dalle_loss_fn(cfg, mesh, batch_axis="dp",
                                   impl=args.sp_impl)
    elif args.pp and args.pp > 1:
        # pipeline-parallel training: depth/pp layers per stage, GPipe
        # microbatching inside one shard_map
        from dalle_pytorch_tpu.parallel import pp_dalle_loss_fn
        loss_fn = pp_dalle_loss_fn(
            cfg, mesh, dp_axis="dp",
            num_microbatches=args.pp_microbatches or None)
    else:
        caption_drop = args.caption_drop

        def loss_fn(params, batch, rng):
            # all-True mask, matching the reference's training call
            # (trainDALLE.py:192); image ids are precomputed outside the step
            text = batch["text"]
            if caption_drop > 0:
                # per-sample null caption (all PAD) so the model learns the
                # unconditional distribution guidance extrapolates against
                drop = jax.random.bernoulli(
                    jax.random.fold_in(rng, 0x0CFD),
                    caption_drop, (text.shape[0], 1))
                text = jnp.where(drop, 0, text)
            mask = jnp.ones_like(text, bool)
            return D.dalle_apply(params, text, batch["image"],
                                 cfg=cfg, mask=mask, rng=rng, train=True,
                                 return_loss=True)

    step = make_train_step(loss_fn, optimizer,
                           grad_accum=args.grad_accum)
    from dalle_pytorch_tpu.cli.common import make_ema
    ema, ema_update = make_ema(args, params, resume_path or "")

    # mutable loop state the supervisor's save_state closure reads live
    # (run_supervised_loop advances it)
    state = LoopState(epoch=start_epoch,
                      global_step=plan["global_step"] if plan else 0)

    def save_state(path):
        return ckpt.save(
            path, params, step=state.global_step, config=cfg,
            opt_state=opt_state, kind="dalle",
            meta={"epoch": state.epoch, "step_in_epoch": state.epoch_i,
                  "global_step": state.global_step,
                  "records_in_epoch": state.records_in_epoch,
                  "train_loss": state.train_loss,
                  "n_batches": state.n_batches, "vae_checkpoint": vae_path,
                  "vocab_words": len(vocab), "lr_schedule": sched,
                  **({"ema_decay": args.ema_decay} if ema is not None
                     else {})}, ema=ema)

    sup = make_supervisor(args, metrics, ckpt_name, save_state)
    if resume_path:
        # the checkpoint we just restored from is a valid rollback
        # anchor — without it a NaN before the first cadence/epoch
        # save after resume would raise instead of rolling back
        sup.register_checkpoint(resume_path)

    def train_step(hosted, state):
        nonlocal params, opt_state, ema
        # explicit device_put on the host-decoded pixel batch: the VAE
        # tokenizer jit must not rely on an implicit transfer (the body
        # runs under --guard_transfers; shard_batch and step_rng are
        # already explicit)
        image_ids = tokenize(jax.device_put(hosted["images"]))
        batch = shard_batch(mesh, {"text": hosted["text"],
                                   "image": image_ids})
        batch = sup.pre_step(state.global_step, batch)
        params, opt_state, loss = step(
            params, opt_state, batch,
            step_rng(key, state.global_step, mesh))
        if ema is not None:
            ema = ema_update(ema, params)
        return loss, batch["text"]

    def on_rollback(state):
        nonlocal params, opt_state, ema
        params, opt_state, ema = restore_rollback(
            sup, optimizer, mesh, param_specs=param_specs)

    def on_epoch_end(state, avg):
        epoch = state.epoch
        path = ckpt.save(
            ckpt.ckpt_path(args.models_dir, ckpt_name, epoch),
            params, step=epoch, config=cfg, opt_state=opt_state,
            kind="dalle",
            meta={"epoch": epoch, "avg_loss": avg,
                  "global_step": state.global_step,
                  "vae_checkpoint": vae_path, "vocab_words": len(vocab),
                  "lr_schedule": sched,
                  **({"ema_decay": args.ema_decay} if ema is not None
                     else {})},
            ema=ema)
        metrics.event(event="checkpoint", path=path, epoch=epoch,
                      avg_loss=avg)

        if args.sample_every and (epoch + 1) % args.sample_every == 0 \
                and state.last is not None:
            # sample from the last minibatch's captions (reference
            # :215-217) — allgathered so all hosts feed the sampler
            # identically (see train_vae's grid path). A resume landing
            # exactly on the epoch boundary has no batch in hand.
            from dalle_pytorch_tpu.parallel.multihost import fetch_local
            texts = fetch_local(state.last)
            k = min(4, texts.shape[0])
            images = D.generate_images(
                params, vae_params, jnp.asarray(texts[:k]), cfg=cfg,
                rng=jax.random.fold_in(key, 10_000 + epoch))
            out = os.path.join(args.results_dir,
                               f"{args.name}_dalle_epoch_{epoch}.png")
            save_image_grid(np.asarray(images), out, nrow=k)
            metrics.event(event="sample", path=out, epoch=epoch)
        return path

    run_supervised_loop(
        args, sup=sup, metrics=metrics, profiler=profiler, dataset=dataset,
        plan=plan, state=state, train_step=train_step,
        on_rollback=on_rollback, on_epoch_end=on_epoch_end,
        transform=load_batch,
        units_of=lambda item: args.batchSize * cfg.seq_len)


if __name__ == "__main__":
    main()

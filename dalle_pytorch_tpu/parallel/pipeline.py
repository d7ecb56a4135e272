"""Pipeline parallelism: GPipe-style microbatched transformer over a ``pp``
mesh axis.

The reference has no pipeline (or any) parallelism (SURVEY.md §2.12/§2b);
this is new TPU-native design: the depth-stacked layer tree is sharded so
each of the P pipeline stages holds ``depth/P`` consecutive layers, the
batch splits into M microbatches, and activations flow stage-to-stage with
``lax.ppermute`` over ICI inside one ``shard_map`` program. The schedule is
the classic (M + P - 1)-tick pipeline: at tick t, stage s runs microbatch
``t - s`` (when in range) through its layer slice; XLA overlaps each tick's
neighbor transfer with compute.

Everything is a single jit-compiled SPMD program — no userland send/recv
runtime — and it is differentiable end to end: the scan-over-ticks
transposes into the reverse pipeline schedule and the ``ppermute`` into the
reverse rotation.

Composes with data parallelism by sharding the microbatch dimension over a
``dp`` axis of the same mesh (``dp_axis=``); tensor/sequence parallelism
apply within a stage exactly as without pp. MoE layers compose too (r5):
the tick scan threads the Switch load-balance aux through to the loss,
and ``pp_param_specs(ep=...)`` shards each stage's expert stacks over an
``ep`` mesh axis that rides the shard_map as a GSPMD auto axis —
dp x pp x ep in one program (dryrun-proven with loss parity).
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
# shard_map(axis_names=...) keeps non-pipeline mesh axes (e.g. 'ep') as
# GSPMD auto axes
from jax import lax, shard_map
from jax.sharding import Mesh, PartitionSpec as P

Array = jax.Array


def _stage_pattern(cfg, num_stages: int):
    """Per-stage sparse pattern — must be identical across stages (the
    stage body is one SPMD program; a stage-dependent pattern would need a
    traced cond, which the static-unroll design deliberately avoids)."""
    depth_per = cfg.depth // num_stages
    pattern = cfg.sparse_pattern
    slices = {pattern[s * depth_per:(s + 1) * depth_per]
              for s in range(num_stages)}
    if len(slices) != 1:
        raise ValueError(
            f"sparse pattern {pattern} is not stage-invariant over "
            f"{num_stages} pipeline stages of {depth_per} layers — every "
            "stage must see the same dense/sparse slice")
    return next(iter(slices))


def pipeline_transformer(params, x: Array, *, cfg, mesh: Mesh,
                         axis: str = "pp",
                         num_microbatches: Optional[int] = None,
                         dp_axis: Optional[str] = None,
                         mask: Optional[Array] = None,
                         rng=None, train: bool = False,
                         with_aux: bool = False):
    """Run the transformer stack pipelined over ``mesh.shape[axis]`` stages.

    params: depth-stacked layer tree (leading axis ``cfg.depth``).
    x: (b, n, dim); b must divide into ``num_microbatches`` (default = the
    stage count P; more microbatches shrink the P-1-tick bubble).
    mask: optional (b, n) pad mask, routed to attention per microbatch.
    dp_axis: additionally shard the microbatch dimension over this mesh
    axis (pipeline x data parallel in one program).
    rng/train: dropout, keyed per (stage, microbatch) — deterministic for a
    given rng, stage count, and microbatch split.

    Returns the same (b, n, dim) as ``transformer_apply`` on one device —
    parity-tested on the CPU mesh (grad parity too: the scan-over-ticks and
    the ppermute both transpose). ``reversible=True`` is rejected
    (different math). Idle ramp-up/ramp-down ticks skip the stage compute
    with ``lax.cond`` (local control flow is legal inside shard_map; the
    collective stays outside the branch).
    """
    from dalle_pytorch_tpu.ops.transformer import transformer_apply

    num_stages = mesh.shape[axis]
    if cfg.depth % num_stages:
        raise ValueError(f"depth {cfg.depth} not divisible by pipeline "
                         f"stages {num_stages}")
    if cfg.reversible:
        # the reversible engine's two-stream math differs from the plain
        # stack — running it as sequential stages would silently change the
        # function; pp + reversible is a future combination
        raise NotImplementedError(
            "pipeline_transformer does not support reversible=True")
    dropout_on = train and (cfg.attn_dropout > 0 or cfg.ff_dropout > 0)
    if dropout_on and rng is None:
        raise ValueError(
            "pipeline_transformer(train=True) with nonzero dropout requires "
            "an explicit `rng` key — JAX has no global RNG state")
    depth_per = cfg.depth // num_stages
    stage_cfg = dataclasses.replace(
        cfg, depth=depth_per, sparse_attn=_stage_pattern(cfg, num_stages))

    M = num_microbatches or num_stages
    b, n, d = x.shape
    if b % M:
        raise ValueError(f"batch {b} not divisible by {M} microbatches")
    mb = b // M

    # stage-major layer stack: (P, depth/P, ...), stage axis sharded on pp
    stacked = jax.tree.map(
        lambda a: a.reshape(num_stages, depth_per, *a.shape[1:]), params)
    xm = x.reshape(M, mb, n, d)
    has_mask = mask is not None
    maskm = (mask.reshape(M, mb, n) if has_mask
             else jnp.ones((M, 1, 1), bool))              # dead placeholder
    if rng is None:
        rng = jax.random.PRNGKey(0)          # dead value (dropout off)

    def stage_fn(stage_params, xm, maskm, rng):
        sp = jax.tree.map(lambda a: a[0], stage_params)   # local layer slice
        # static stage count from the enclosing mesh (== the manual axis
        # size)
        P_ = num_stages
        idx = lax.axis_index(axis)
        ticks = M + P_ - 1
        # pad the input stream so ticks beyond M feed (ignored) zeros
        pad = jnp.zeros((P_ - 1, *xm.shape[1:]), xm.dtype)
        stream = jnp.concatenate([xm, pad], axis=0)
        # the microbatch at this stage at tick t is t - idx: pre-gather each
        # tick's pad mask per stage (clipped; out-of-range ticks are idle
        # and their outputs never selected)
        masks = jax.vmap(
            lambda t: maskm[jnp.clip(t - idx, 0, M - 1)])(jnp.arange(ticks))
        rng_stage = jax.random.fold_in(rng, idx)

        def tick(state, xs):
            t, inp, m_in = xs
            # stage 0 ingests the next microbatch; others use the handoff
            h = jnp.where(idx == 0, inp, state)
            m = m_in if has_mask else None
            mb_idx = t - idx
            key_mb = jax.random.fold_in(rng_stage,
                                        jnp.clip(mb_idx, 0, M - 1))

            def run(h):
                return transformer_apply(sp, h, cfg=stage_cfg, mask=m,
                                         rng=key_mb, train=train,
                                         with_aux=True)

            # ramp-up/down ticks where this stage holds no microbatch skip
            # the layer slice entirely (identity); the ppermute below runs
            # unconditionally so the collective stays program-aligned
            active = jnp.logical_and(mb_idx >= 0, mb_idx < M)
            # the idle branch's zero aux must carry the same varying axes
            # as the active branch's: a real MoE aux inherits (pp, dp)
            # from the activations, while the dense stack's aux is a
            # literal 0.0 constant (non-varying) — match each case
            if cfg.moe_experts:
                zero_aux = lax.pcast(
                    jnp.float32(0.0),
                    tuple(a for a in (axis, dp_axis) if a is not None),
                    to="varying")
            else:
                zero_aux = jnp.float32(0.0)
            out, aux = lax.cond(active, run, lambda h: (h, zero_aux), h)
            nxt = lax.ppermute(out, axis,
                               [(i, (i + 1) % P_) for i in range(P_)])
            return nxt, (out, aux)

        # the carry is device-varying over pp (each stage holds a different
        # microbatch's activations) — mark the zero init accordingly
        state0 = lax.pcast(jnp.zeros_like(xm[0]), (axis,), to="varying")
        _, (outs, auxs) = lax.scan(tick, state0,
                                   (jnp.arange(ticks), stream[:ticks],
                                    masks))
        # stage s finishes microbatch m at tick m + s: the last stage's
        # outputs at ticks P-1 .. M+P-2 are the final activations, in order
        final = outs[P_ - 1:]
        final = jnp.where(idx == P_ - 1, final, jnp.zeros_like(final))
        # MoE load-balance aux: every stage contributes its layer slice's
        # aux for each ACTIVE tick (idle ticks contribute the cond's 0).
        # Match the dense path's normalization (one batch-wide MEAN per
        # layer, summed over layers — moe.py:124): sum stages via psum
        # over pp, average the M microbatch means, and pmean over dp so
        # the scalar leaves the shard_map replicated
        aux_total = lax.psum(auxs.sum(), axis) / M
        if dp_axis is not None:
            aux_total = lax.pmean(aux_total, dp_axis)
        return lax.psum(final, axis), aux_total           # select last stage

    data_spec = P(None, dp_axis) if dp_axis else P()
    mask_spec = data_spec if has_mask else P()    # placeholder: replicate
    # manual only over pp (+ dp for the data specs): any OTHER mesh axis
    # (e.g. 'ep' sharding each stage's expert stacks) stays a GSPMD auto
    # axis and composes without this file knowing it exists — the same
    # partial-manual discipline as parallel.sequence
    manual = frozenset(a for a in (axis, dp_axis) if a is not None)
    out, aux = shard_map(stage_fn, mesh=mesh,
                         in_specs=(P(axis), data_spec, mask_spec, P()),
                         out_specs=(data_spec, P()),
                         axis_names=manual)(stacked, xm, maskm, rng)
    out = out.reshape(b, n, d)
    return (out, aux) if with_aux else out


def pp_param_specs(params, axis: str = "pp", ep: Optional[str] = None):
    """PartitionSpecs that shard the depth-stacked transformer over the
    pipeline axis (each stage stores only its own depth/P layer slice; the
    contiguous leading-axis shard is exactly the stage-major reshape inside
    ``pipeline_transformer``) and replicate everything else. Feed to
    ``parallel.train.setup_sharded(param_specs=...)``.

    ``ep`` additionally shards the MoE expert axis of each stage's layer
    slice over that mesh axis — dp x pp x ep in one program (the expert
    axis is a GSPMD auto axis inside the pipeline's shard_map)."""
    specs = {k: (jax.tree.map(lambda _: P(axis), v) if k == "transformer"
                 else jax.tree.map(lambda _: P(), v))
             for k, v in params.items()}
    if ep is not None:
        if "moe" not in specs.get("transformer", {}).get("ff", {}):
            # a layout drift must surface, not silently degrade to
            # replicated experts (ADVICE r5 #3): the caller asked for
            # expert parallelism and would quietly lose it
            raise ValueError(
                f"ep={ep!r} requested but the param tree has no "
                "['transformer']['ff']['moe'] subtree — the model was "
                "built without MoE (moe_experts=0) or the MoE param "
                "layout moved; update pp_param_specs' path to match")
        moe = specs["transformer"]["ff"]["moe"]
        moe["w1"] = P(axis, ep)          # (depth, E, dim, hidden)
        moe["w2"] = P(axis, ep)
    return specs


def pp_dalle_loss_fn(cfg, mesh: Mesh, *, axis: str = "pp",
                     dp_axis: Optional[str] = None,
                     num_microbatches: Optional[int] = None):
    """DALLE training loss with the transformer pipelined over ``axis`` —
    the pp counterpart of ``parallel.sequence.sp_dalle_loss_fn``.

    Batch = {'text': (b, t) ids, 'image': (b, n_img) token ids, 'mask':
    optional (b, t) text pad mask, extended all-True over the image span
    like the dense path (reference dalle_pytorch.py:384-388)}. Embedding
    lookups and the CE head run under GSPMD outside the pipeline;
    ``cfg.loss_chunk`` caps the head's logits memory as usual. Signature
    matches ``parallel.train.make_train_step``'s
    ``loss_fn(params, batch, rng)``.
    """
    from dalle_pytorch_tpu.models import dalle as D
    if cfg.transformer.reversible:
        raise NotImplementedError(
            "pipeline parallelism does not support reversible=True")

    def loss(params, batch, rng):
        text, image_ids = batch["text"], batch["image"]
        tokens = D.embed_prompt(params, cfg, text, image_ids)
        mask = batch.get("mask")
        if mask is not None:
            pad = jnp.ones((mask.shape[0], image_ids.shape[1]), bool)
            mask = jnp.concatenate([mask, pad], axis=1)
        h, aux = pipeline_transformer(params["transformer"], tokens,
                                      cfg=cfg.transformer, mesh=mesh,
                                      axis=axis, dp_axis=dp_axis,
                                      num_microbatches=num_microbatches,
                                      mask=mask, rng=rng, train=True,
                                      with_aux=True)
        # same loss tail as dalle_apply — one definition of the contract
        loss_val = D.ce_from_hidden(params, h, text, image_ids, cfg=cfg)
        if cfg.moe_experts:
            # GPipe sums aux over stages x microbatches; dalle_apply's
            # dense scan sums over layers for the whole batch — same
            # total, same coefficient (models/dalle.py:281-282)
            loss_val = loss_val + cfg.moe_aux_coef * aux
        return loss_val

    return loss

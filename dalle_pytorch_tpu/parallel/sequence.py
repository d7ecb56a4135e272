"""Sequence-parallel transformer: the full stack with the TOKEN axis
sharded over a mesh axis — long-context training the reference cannot do at
all (SURVEY.md §5.7: its only sequence-cost levers are single-device).

Layout: activations are (batch, seq/sp, dim) per device; parameters are
replicated over ``sp`` (shard them over dp/fsdp outside). LayerNorm, the
qkv/out projections, and the GEGLU FF are position-local, so they need no
communication; only attention mixes positions and it runs as either

  * ``impl='ring'``   — K/V shards rotate neighbor-to-neighbor with
    ``ppermute`` (bandwidth-optimal on an ICI ring) into an online-softmax
    accumulator (parallel.ring.ring_attention_local), or
  * ``impl='ulysses'`` — one all-to-all re-shards sequence -> heads, local
    dense attention over the full sequence, all-to-all back.

The whole stack is ONE ``shard_map`` (collectives inside a single compiled
program, one ``lax.scan`` over the depth-stacked layer params) rather than
a shard_map per attention call.

Pad masks are supported with dense-path semantics: mask blocks rotate
around the ring with k/v (pad pairs fill with the finite -fmax, so padded
rows degrade to a causal-prefix average exactly like
ops.attention.dense_attention_weights). Dropout is supported and
sp-degree-invariant: both dropout sites (post-attention projection, FF
hidden) are position-local, so their masks are drawn from PER-POSITION
keys (``core.positional_dropout`` with offset = shard start) — the same
rng gives bit-identical masks on every sp degree, and the flagship
dropout-0.1 config trains under ``--sp``. ``cfg.remat`` composes (the
checkpointed body re-runs its ring/all-to-all collectives in the
backward), as do extra GSPMD mesh axes: only sp/batch are manual
(``shard_map(axis_names=...)``), so tp/fsdp param shardings ride through
— dp x tp x sp with remat is the long-context training recipe.
Restrictions (asserted): dense attention only, no reversible engine.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
# shard_map(axis_names=...) is partial-manual lowering: unnamed mesh axes
# stay GSPMD auto axes
from jax import lax, shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from dalle_pytorch_tpu.ops import attention as attn_ops
from dalle_pytorch_tpu.ops import core
from dalle_pytorch_tpu.ops import transformer as T
from dalle_pytorch_tpu.parallel.ring import (ring_attention_local,
                                             ulysses_attention_local)


def _check_cfg(cfg: T.TransformerConfig) -> None:
    if any(cfg.sparse_pattern):
        raise ValueError("sequence parallelism supports dense attention "
                         "only (sparse_attn must be False)")
    if cfg.reversible:
        raise ValueError("sequence parallelism and reversible execution "
                         "are mutually exclusive engines")
    if cfg.moe_experts:
        raise ValueError("sequence parallelism does not yet compose with "
                         "MoE layers (route tokens before sharding them)")


def sp_transformer_apply(params, x, *, cfg: T.TransformerConfig, mesh: Mesh,
                         sp_axis: str = "sp",
                         batch_axis: Optional[str] = None,
                         impl: str = "ring", mask=None,
                         rng=None, train: bool = False):
    """Run the stack with x (b, n, dim) sequence-sharded over ``sp_axis``.

    Numerics match ``ops.transformer.transformer_apply`` (same prenorm
    residual bodies, same ``cfg.scale``, same pad-mask semantics — ``mask``
    is the (b, n) GLOBAL pad mask, sharded like the tokens); only the
    attention communication pattern differs. ``batch_axis`` optionally
    shards the batch dim too (dp x sp in one mesh). Dropout masks are drawn
    per GLOBAL token position (core.positional_dropout), so the same
    ``rng`` yields identical masks on every sp degree.
    """
    _check_cfg(cfg)
    if impl not in ("ring", "ulysses"):
        raise ValueError(f"unknown sp impl {impl!r}")
    dropout_on = train and (cfg.attn_dropout > 0 or cfg.ff_dropout > 0)
    if dropout_on and rng is None:
        raise ValueError(
            "sp_transformer_apply(train=True) with nonzero dropout requires "
            "an explicit `rng` key — JAX has no global RNG state")
    size = mesh.shape[sp_axis]
    if x.shape[1] % size != 0:
        raise ValueError(f"seq len {x.shape[1]} not divisible by "
                         f"{sp_axis} axis ({size})")
    n_local = x.shape[1] // size
    keys = T._layer_keys(rng, cfg.depth)

    def attend(q, k, v, mb):
        if impl == "ring":
            return ring_attention_local(q, k, v, axis=sp_axis, size=size,
                                        causal=cfg.causal, scale=cfg.scale,
                                        mask=mb)
        return ulysses_attention_local(q, k, v, axis=sp_axis,
                                       causal=cfg.causal, scale=cfg.scale,
                                       mask=mb)

    def stack(params, keys, x, mb):
        # absolute position of this shard's first token — the dropout keys
        # depend on it, not on the shard index count, hence sp-invariance
        offset = lax.axis_index(sp_axis) * n_local

        def body(h, xs):
            lp, lkeys = xs
            a_in = core.layernorm(lp["attn"]["ln"], h)
            q, k, v = attn_ops.qkv_project(lp["attn"], a_in, cfg.heads)
            o = attend(q, k, v, mb)
            a_out = attn_ops.output_tail(lp["attn"], o)
            a_out = core.positional_dropout(lkeys[0], a_out,
                                            cfg.attn_dropout, train,
                                            offset=offset)
            h = h + a_out
            h = h + T.ff_branch(
                lp, h, cfg, lkeys[1], train,
                dropout_fn=lambda k, t: core.positional_dropout(
                    k, t, cfg.ff_dropout, train, offset=offset))
            return h, None

        # remat composes with sequence sharding: jax.checkpoint inside the
        # shard_map body re-runs the layer (including the ring ppermutes /
        # the ulysses all-to-alls) in the backward — activation thrift and
        # sequence sharding together are exactly the long-context recipe
        out, _ = lax.scan(T._maybe_remat(body, cfg.remat), x, (params, keys))
        return out

    x_spec = P(batch_axis, sp_axis, None)
    m_spec = P(batch_axis, sp_axis)
    # Only the token/batch axes are MANUAL (ring ppermutes / all-to-alls
    # written by hand); every other mesh axis stays auto, so e.g. a
    # dp x tp x sp mesh runs Megatron tp INSIDE this shard_map with
    # GSPMD-placed collectives — the 3-axis long-context recipe — without
    # this file knowing tp exists. Params use in_specs P(): replicated
    # over the manual axes, while any auto-axis sharding (tp/fsdp) rides
    # through untouched.
    manual = frozenset(a for a in (sp_axis, batch_axis) if a is not None)
    if mask is None:
        return shard_map(lambda p, k, x: stack(p, k, x, None), mesh=mesh,
                         in_specs=(P(), P(), x_spec),
                         out_specs=x_spec,
                         axis_names=manual)(params, keys, x)
    return shard_map(stack, mesh=mesh, in_specs=(P(), P(), x_spec, m_spec),
                     out_specs=x_spec, axis_names=manual)(params, keys, x,
                                                          mask)


def sp_dalle_loss_fn(cfg, mesh: Mesh, *, sp_axis: str = "sp",
                     batch_axis: Optional[str] = None, impl: str = "ring"):
    """DALLE training loss with the transformer sequence-sharded.

    Batch = {'text': (b, t) ids, 'image': (b, n_img) token ids, 'mask':
    optional (b, t) text pad mask — extended all-True over the image span
    exactly like the dense path (reference dalle_pytorch.py:384-388)}.
    Embedding lookups and the CE head run under GSPMD (the embeddings
    inherit the sequence sharding from the concat; use ``cfg.loss_chunk``
    to also cap the head's logits memory). Signature matches
    ``parallel.train.make_train_step``'s ``loss_fn(params, batch, rng)``.
    """
    from dalle_pytorch_tpu.models import dalle as D
    _check_cfg(cfg.transformer)

    def loss(params, batch, rng):
        text, image_ids = batch["text"], batch["image"]
        tokens = D.embed_prompt(params, cfg, text, image_ids)
        tokens = jax.lax.with_sharding_constraint(
            tokens, NamedSharding(mesh, P(batch_axis, sp_axis, None)))
        mask = batch.get("mask")
        if mask is not None:
            pad = jnp.ones((mask.shape[0], image_ids.shape[1]), bool)
            mask = jnp.concatenate([mask, pad], axis=1)
        h = sp_transformer_apply(params["transformer"], tokens,
                                 cfg=cfg.transformer, mesh=mesh,
                                 sp_axis=sp_axis, batch_axis=batch_axis,
                                 impl=impl, mask=mask, rng=rng, train=True)
        # same loss tail as dalle_apply — one definition of the contract
        return D.ce_from_hidden(params, h, text, image_ids, cfg=cfg)

    return loss

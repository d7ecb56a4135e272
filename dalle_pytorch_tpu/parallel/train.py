"""Sharded training steps: jit + NamedShardings, collectives by XLA.

The idiomatic TPU recipe (scaling-book style): pick a mesh, place params and
batch with NamedShardings, jit the step — XLA/GSPMD inserts the gradient
psum over `dp`, the all-gathers/reduce-scatters implied by `fsdp`, and the
activation collectives implied by `tp`, all riding ICI. There is no userland
communication library to port (the reference has none anyway, SURVEY.md
§2.12); the mesh IS the backend.

Usage:
    mesh = make_mesh({'dp': 4, 'tp': 2})
    specs = dalle_param_specs(params, tp='tp')           # or fsdp='dp'
    params, opt_state = setup_sharded(params, optimizer, mesh, specs)
    step = make_train_step(loss_fn, optimizer)
    batch = shard_batch(mesh, batch)
    params, opt_state, loss = step(params, opt_state, batch, rng)
"""

from __future__ import annotations

import functools
from typing import Callable, Optional

import jax
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from dalle_pytorch_tpu.parallel import _compat



def make_train_step(loss_fn: Callable, optimizer,
                    grad_accum: int = 1) -> Callable:
    """jit step: (params, opt_state, batch, rng) -> (params, opt_state, loss).

    ``loss_fn(params, batch, rng) -> scalar``. Shardings are dictated by the
    inputs (set up with ``setup_sharded``/``shard_batch``); params and opt
    state buffers are donated.

    ``grad_accum > 1`` splits the batch's leading dim into that many
    microbatches and accumulates their mean gradient in a ``lax.scan``
    before the single optimizer update — the same update as the full batch
    when the loss is deterministic (it is an example mean); with RNG in the
    loss (dropout, Gumbel noise) each microbatch gets an independent
    ``fold_in``-derived key, so noise stays decorrelated across the
    accumulated batch (not bitwise the full-batch draw). Activation memory
    is 1/N. The batch must be a dict; scalar entries (e.g. a traced
    temperature) pass through unsplit, array entries' leading dim must
    divide.

    An optional scalar ``batch['lr_scale']`` multiplies the optimizer
    updates (for Adam, exactly an LR scale) — the resilience supervisor's
    post-rollback re-warm rides it as a traced input, so the ramp never
    recompiles. Absent key = scale 1.
    """

    # donation frees the old params/opt-state in place; CPU ignores it
    donate = _compat.donate_if_accelerator(0, 1)

    @functools.partial(jax.jit, donate_argnums=donate)
    def step(params, opt_state, batch, rng):
        batch = dict(batch)
        lr_scale = batch.pop("lr_scale", None)
        if grad_accum <= 1:
            loss, grads = jax.value_and_grad(loss_fn)(params, batch, rng)
        else:
            loss, grads = accumulate_grads(loss_fn, params, batch, rng,
                                           grad_accum)
        with jax.named_scope("optimizer"):
            updates, opt_state = optimizer.update(grads, opt_state, params)
            if lr_scale is not None:
                updates = jax.tree.map(
                    lambda u: (u * lr_scale).astype(u.dtype), updates)
            params = optax.apply_updates(params, updates)
        return params, opt_state, loss

    return step


def step_scopes(step, *args) -> dict:
    """{instruction name: scope entry} of a jitted step compiled for
    ``args`` (arrays, or ``ShapeDtypeStruct``s with shardings: nothing
    runs and nothing is donated): the join from a profiler capture's
    device events to the model's named scopes (``obs/device.py``). Not on
    any hot path: it compiles the step once more (a hit in the persistent
    compile cache only from an earlier call with the same code)."""
    from dalle_pytorch_tpu.obs import device as odev
    return odev.scopes_of_lowered(step.lower(*odev.abstract(args)))


def accumulate_grads(loss_fn: Callable, params, batch: dict, rng,
                     grad_accum: int):
    """(mean loss, mean grads) over ``grad_accum`` microbatches, scanned so
    only one microbatch's activations are live at a time. ``batch`` is a
    dict; entries with ndim >= 1 split on their leading dim, scalars are
    closed over unchanged. Each microbatch's loss sees a distinct
    ``fold_in(rng, i)`` key — identical keys would correlate dropout/noise
    across the whole accumulated batch."""
    import jax.numpy as jnp
    if not isinstance(batch, dict):
        raise TypeError("grad accumulation expects a dict batch")
    split = {k: v for k, v in batch.items()
             if getattr(v, "ndim", 0) >= 1}
    rest = {k: v for k, v in batch.items() if k not in split}
    micro = jax.tree.map(
        lambda a: a.reshape(grad_accum, a.shape[0] // grad_accum,
                            *a.shape[1:]), split)

    def body(carry, xs):
        i, mb = xs
        loss_acc, grads_acc = carry
        loss_i, grads_i = jax.value_and_grad(loss_fn)(
            params, {**mb, **rest}, jax.random.fold_in(rng, i))
        grads_acc = jax.tree.map(
            lambda a, g: a + g.astype(jnp.float32), grads_acc, grads_i)
        return (loss_acc + loss_i, grads_acc), None

    # accumulate in f32 even under --param_dtype bfloat16: bf16 summation
    # across microbatches compounds rounding error as grad_accum grows
    zeros = jax.tree.map(
        lambda p: jnp.zeros(p.shape, jnp.float32), params)
    (loss, grads), _ = jax.lax.scan(
        body, (jnp.float32(0.0), zeros), (jnp.arange(grad_accum), micro))
    inv = 1.0 / grad_accum
    return loss * inv, jax.tree.map(
        lambda g, p: (g * inv).astype(p.dtype), grads, params)


def setup_sharded(params, optimizer, mesh: Mesh, param_specs=None,
                  opt_state=None):
    """Place params per ``param_specs`` (replicated when None) and build the
    optimizer state THROUGH jit so its moment buffers inherit the param
    shardings (the standard GSPMD propagation trick). A restored
    ``opt_state`` (checkpoint resume) is placed like the params instead of
    re-initialized."""
    if param_specs is None:
        shardings = NamedSharding(mesh, P())
        params = jax.device_put(params, shardings)
        if opt_state is not None:
            opt_state = jax.device_put(opt_state, shardings)
    else:
        shardings = jax.tree.map(
            lambda s: NamedSharding(mesh, s), param_specs,
            is_leaf=lambda x: isinstance(x, P))
        params = jax.tree.map(jax.device_put, params, shardings)
        if opt_state is not None:
            # moment buffers mirror the param TREE (optax mu/nu subtrees have
            # the params' exact structure): place each such subtree with the
            # params' own sharding tree — matched positionally by path, never
            # by array shape (two equal-shaped params with different specs
            # must not collide) — and replicate everything else (counters).
            p_struct = jax.tree.structure(params)
            p_leaves = jax.tree.leaves(params)

            def is_param_tree(x):
                if jax.tree.structure(x) != p_struct:
                    return False
                return all(getattr(a, "shape", None) == b.shape
                           for a, b in zip(jax.tree.leaves(x), p_leaves))

            opt_state = jax.tree.map(
                lambda sub: (jax.tree.map(jax.device_put, sub, shardings)
                             if is_param_tree(sub)
                             else jax.device_put(
                                 sub, NamedSharding(mesh, P()))),
                opt_state, is_leaf=is_param_tree)
    if opt_state is None:
        opt_state = jax.jit(optimizer.init)(params)
        # leaves that do not depend on the params (adam's step count) are
        # constants jit materializes on the default device alone; place
        # them on the mesh HERE, explicitly, or the first train step moves
        # them with an implicit device-to-device transfer (which
        # --guard_transfers refuses on any mesh wider than one device)
        devices = set(mesh.devices.flat)
        opt_state = jax.tree.map(
            lambda a: a if a.sharding.device_set == devices
            else jax.device_put(a, NamedSharding(mesh, P())), opt_state)
    return params, opt_state


# ---------------------------------------------------------------------------
# partition-spec rules for the framework's parameter trees
# ---------------------------------------------------------------------------

def _dalle_rule(tp: Optional[str], fsdp: Optional[str]):
    """Spec by (sub-module, leaf) name for DALLE/transformer params.

    Transformer layer params are depth-stacked (leading depth axis) — that
    axis shards over ``fsdp`` (ZeRO-style: each device stores a slice of
    every layer stack, all-gathered per scan step). ``tp`` follows the
    Megatron pattern: qkv/w1 column-parallel, out/w2 row-parallel, so each
    layer needs exactly one psum on the attention output and one on the FF
    output — inserted by XLA from the shardings alone.
    """
    def rule(path, leaf):
        keys = [getattr(k, "key", None) for k in path]
        # layer-stack params are recognized by their attn/ff sub-keys, so a
        # BARE transformer tree (no 'transformer' ancestor) shards the same
        # as one nested inside DALLE/CLIP params
        if "attn" in keys or "ff" in keys:
            sub, name = keys[-2], keys[-1]
            if name == "w":
                if sub in ("qkv", "w1"):
                    return P(fsdp, None, tp)      # column parallel
                if sub in ("out", "w2"):
                    return P(fsdp, tp, None)      # row parallel
            if name == "b" and sub == "w1":
                return P(fsdp, tp)
            return P(fsdp)                         # ln params, out/w2 bias
        if keys[-2] == "proj":                     # to_logits
            return P(None, tp) if keys[-1] == "w" else P(tp)
        return P()                                 # embeddings replicated
    return rule


def dalle_param_specs(params, tp: Optional[str] = None,
                      fsdp: Optional[str] = None,
                      mesh: Optional[Mesh] = None):
    """PartitionSpec tree for a DALLE (or bare transformer) param tree.

    With ``mesh``, any axis whose dimension is not divisible by the mesh
    axis size is dropped back to replicated for that dim (e.g. the
    total_tokens logits dim with an odd vocab size).
    """
    rule = _dalle_rule(tp, fsdp)

    def checked(path, leaf):
        spec = rule(path, leaf)
        if mesh is None:
            return spec
        fixed = tuple(
            a if (a is None or leaf.shape[i] % mesh.shape[a] == 0) else None
            for i, a in enumerate(spec))
        return P(*fixed)

    return jax.tree_util.tree_map_with_path(checked, params)


def dalle_moe_param_specs(params, axis: str = "ep"):
    """PartitionSpecs sharding the MoE expert axis over ``axis``: the
    depth-stacked expert weights (depth, E, ...) get P(None, axis); the
    router and everything else replicate. Feed to
    ``setup_sharded(param_specs=...)`` on a dp x ep mesh — GSPMD inserts
    the token->expert collectives."""
    specs = jax.tree.map(lambda _: P(), params)
    moe = specs["transformer"]["ff"]["moe"]
    moe["w1"] = P(None, axis)
    moe["w2"] = P(None, axis)
    return specs


# ---------------------------------------------------------------------------
# model-specific loss closures
# ---------------------------------------------------------------------------

def vae_loss_fn(cfg, *, smooth_l1: bool = False, temperature=None):
    """Batch = {'images': (b, H, W, C)}. The training scripts' loss is
    smooth_l1 + mse (reference trainVAE.py:87) while the model's built-in is
    mse-only (reference dalle_pytorch.py:156); ``smooth_l1`` selects the
    script behavior."""
    from dalle_pytorch_tpu.models import vae as V
    import jax.numpy as jnp

    def loss(params, batch, rng):
        imgs = batch["images"]
        recon = V.vae_apply(params, imgs, cfg=cfg, rng=rng,
                            temperature=temperature)
        mse = jnp.mean(jnp.square(imgs - recon))
        if not smooth_l1:
            return mse
        d = jnp.abs(imgs - recon)
        huber = jnp.mean(jnp.where(d < 1.0, 0.5 * d * d, d - 0.5))
        return huber + mse

    return loss


def dalle_loss_fn(cfg, vae_params=None):
    """Batch = {'text': (b, t), 'image': ids (b, n) or raw images,
    'mask': optional (b, t)}."""
    from dalle_pytorch_tpu.models import dalle as D

    def loss(params, batch, rng):
        return D.dalle_apply(params, batch["text"], batch["image"], cfg=cfg,
                             mask=batch.get("mask"), vae_params=vae_params,
                             rng=rng, train=True, return_loss=True)

    return loss


def clip_loss_fn(cfg):
    from dalle_pytorch_tpu.models import clip as C

    def loss(params, batch, rng):
        return C.clip_apply(params, batch["text"], batch["images"], cfg=cfg,
                            text_mask=batch.get("mask"), return_loss=True)

    return loss

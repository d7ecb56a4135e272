"""Mixture-of-Experts feed-forward — expert-parallel over an ``ep`` axis.

Beyond-reference capability (the reference has no MoE anywhere — SURVEY.md
§2b lists EP/MoE: absent); built because expert parallelism is one of the
first-class distributed axes this framework commits to (dp/tp/fsdp/sp/pp/
ep). The design is the standard dense-dispatch top-k MoE (GShard/Switch
pattern): every routing decision is expressed as einsums over one-hot
dispatch/combine tensors, so the whole layer is static-shaped, jit-friendly,
and shards with nothing but GSPMD sharding annotations —

  * expert-stacked GEGLU weights carry a leading (E, ...) axis; shard it
    over ``ep`` (``moe_param_specs``) and each device stores and runs only
    its E/ep experts;
  * the dispatch einsum produces (E, C, d) expert batches sharded on
    ``ep``; with tokens sharded on ``dp``, XLA inserts the token->expert
    all-to-alls over ICI automatically.

Top-k routing with renormalized gates, capacity C = ceil(T/E * k * cf)
per expert (overflow tokens fall through to the residual — standard
Switch behavior), and the Switch load-balancing auxiliary loss
(mean-prob x token-fraction x E, minimized at uniform routing).
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from dalle_pytorch_tpu.ops import core

Array = jax.Array


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    dim: int
    num_experts: int = 8
    k: int = 2                       # experts per token
    ff_mult: int = 4
    capacity_factor: float = 1.25
    # NOTE: the aux-loss WEIGHT lives with the model objective
    # (DALLEConfig.moe_aux_coef) — moe_apply returns the raw aux loss

    def __post_init__(self):
        if self.k > self.num_experts:
            raise ValueError(
                f"k={self.k} experts per token exceeds num_experts="
                f"{self.num_experts}")


def moe_init(key: Array, cfg: MoEConfig, dtype=jnp.float32) -> dict:
    """Router + expert-stacked GEGLU weights (leading axis = experts)."""
    k_r, k_w1, k_w2 = jax.random.split(key, 3)
    hidden = cfg.dim * cfg.ff_mult
    e = cfg.num_experts

    def stack(k, din, dout):
        keys = jax.random.split(k, e)
        return jax.vmap(
            lambda kk: core.linear_init(kk, din, dout, bias=False,
                                        dtype=dtype)["w"])(keys)

    return {
        "router": core.linear_init(k_r, cfg.dim, e, bias=False,
                                   dtype=dtype),
        "w1": stack(k_w1, cfg.dim, hidden * 2),     # (E, d, 2h) GEGLU in
        "w2": stack(k_w2, hidden, cfg.dim),         # (E, h, d)
    }


def moe_apply(params: dict, x: Array, *, cfg: MoEConfig
              ) -> Tuple[Array, Array]:
    """-> (out (b, n, d), aux load-balance loss scalar).

    Exact dense-dispatch computation, GROUPED per batch row (GShard's
    group semantics): each row routes its n tokens independently with
    capacity C = ceil(n*k/E * cf), so the one-hot dispatch/combine
    tensors are (b, n, E, C) — O(n^2 k cf) per row — instead of the
    O((bn)^2) a flat global queue would cost. Tokens over a row's
    capacity are DROPPED from the expert (they contribute zero here; the
    transformer's residual still carries them — Switch-style graceful
    overflow).
    """
    b, n, d = x.shape
    e, k = cfg.num_experts, cfg.k
    # floor the FINAL capacity at 1 — a 0-width queue would silently zero
    # the whole layer (every token overflows)
    cap = max(1, int(-(-n * k // e) * cfg.capacity_factor))
    cdt = x.dtype

    def group(xt):                                       # (n, d) one row
        logits = core.linear(params["router"], xt.astype(jnp.float32))
        probs = jax.nn.softmax(logits, axis=-1)          # (n, E)
        gate_vals, idx = lax.top_k(probs, k)             # (n, k)
        gate_vals = gate_vals / jnp.maximum(
            gate_vals.sum(axis=-1, keepdims=True), 1e-9)

        onehot = jax.nn.one_hot(idx, e, dtype=jnp.float32)     # (n, k, E)
        # queue position of each token within its expert (first-come)
        ranks = jnp.cumsum(onehot.sum(1), axis=0) - onehot.sum(1)  # (n, E)
        keep = (ranks < cap)[:, None, :] * onehot        # (n, k, E)

        # dispatch: binary (n, E, C); combine: gate-weighted dispatch
        # ranks are whole numbers carried in f32 (a cumsum of one-hots)
        pos = jax.nn.one_hot(ranks.astype(jnp.int32), cap,
                             dtype=jnp.float32)            # (n, E, C)
        dispatch = jnp.einsum("tke,tec->tec", keep, pos)
        combine = jnp.einsum("tke,tk,tec->tec", keep, gate_vals, pos)

        xin = jnp.einsum("tec,td->ecd", dispatch.astype(cdt), xt)
        h = jnp.einsum("ecd,edf->ecf", xin, params["w1"])      # (E, C, 2h)
        h, gates = jnp.split(h, 2, axis=-1)
        h = h * core.gelu(gates)
        eout = jnp.einsum("ecf,efd->ecd", h, params["w2"])     # (E, C, d)
        out = jnp.einsum("tec,ecd->td", combine.astype(cdt), eout)

        # Switch load-balance loss: E * sum_e mean_prob_e * token_frac_e
        aux = e * jnp.sum(onehot[:, 0].mean(axis=0) * probs.mean(axis=0))
        return out, aux

    out, aux = jax.vmap(group)(x)
    return out, jnp.mean(aux).astype(jnp.float32)


def moe_param_specs(axis: str = "ep") -> dict:
    """PartitionSpecs sharding the expert axis over ``axis`` (router
    replicated). Feed into a params-tree spec at the layer's position."""
    from jax.sharding import PartitionSpec as P
    return {"router": {"w": P()}, "w1": P(axis, None, None),
            "w2": P(axis, None, None)}

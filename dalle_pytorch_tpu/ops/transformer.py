"""Transformer stack: PreNorm(attn) + PreNorm(GEGLU-FF) pairs.

Mirrors the reference ``Transformer`` (reference dalle_pytorch/
transformer.py:137-172) — per layer a residual attention block then a
residual feed-forward block, with the pad ``mask`` routed only into attention
(reference reversible.py:8-17, transformer.py:166-167) — but executes the
stack the TPU way:

  * layer parameters are **stacked** on a leading depth axis and the stack
    runs as one ``lax.scan`` — one compiled layer body regardless of depth,
    which is what keeps XLA compile time and code size flat at depth 64;
  * mixed dense/sparse patterns resolve STATICALLY when periodic (the
    reference's ``sparse_attn=(True, False)*32``, period 2): the stack is
    reshaped to (depth/period, period, ...) and the period unrolled in the
    scan body, so no ``lax.cond`` is traced at all; aperiodic patterns
    (period > 4) fall back to a per-layer ``lax.cond`` on a traced flag;
  * ``reversible=True`` swaps the scan for the O(1)-activation-memory
    ``custom_vjp`` engine in ops.reversible (reference reversible.py:54-157);
  * ``remat='full'`` applies ``jax.checkpoint`` to the scanned body —
    the XLA-native activation/compute trade; ``remat='dots'`` checkpoints
    with the ``dots_saveable`` policy instead: matmul outputs stay saved,
    only the cheap vector work (layernorm f32 saves, GEGLU gelu/product
    intermediates — measured ~2/3 of the ~56 MB/layer/batch-element the
    un-rematerialized flash stack saves) is recomputed in the backward,
    so bigger batches fit with near-zero extra MXU FLOPs.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple, Union

import jax
import jax.numpy as jnp
from jax import lax

from dalle_pytorch_tpu.ops import attention as attn_ops
from dalle_pytorch_tpu.ops import core, sparse

Array = jax.Array


class BlockOptionError(ValueError):
    """The ONE refusal of an option that a described block cannot run:
    it names the block and the option, at construction (of a
    configuration, an engine, a cache), never as a trace-time surprise
    and never by silently running something else."""

    def __init__(self, block: str, option: str, why: str = ""):
        super().__init__(
            f"the {block!r} block does not run with {option}"
            + (f": {why}" if why else "")
            + " (it runs through dalle_apply and the paged gather engine:"
              " --kv paged --paged_attn gather)")
        self.block, self.option = block, option


@dataclasses.dataclass(frozen=True)
class LayerKind:
    """The two ways in which a described block's layers differ: a dense
    or a routed feed-forward, and attention over every earlier row
    (``full``) or over a window of them."""
    moe: bool
    full: bool


@dataclasses.dataclass(frozen=True)
class LayerRun:
    """``count`` consecutive layers of one kind, scanned as one
    (``block_stack``): ``at`` is the first one's index in its parameter
    stack (``"moe"`` or ``"dense"``) and ``cache`` its index among the
    layers of its attention type, which is its layer index in that type's
    page pool."""
    kind: LayerKind
    count: int
    at: int
    cache: int

    @property
    def moe(self) -> bool:
        return self.kind.moe

    @property
    def full(self) -> bool:
        return self.kind.full


def layer_runs(blk, depth: int) -> Tuple[LayerRun, ...]:
    """The stack as runs of layers alike in both kinds, in the published
    order: a deeper cut or a whole model is the same code with more runs."""
    kinds = blk.layer_kinds(depth)
    runs, i = [], 0
    while i < depth:
        j = i
        while j < depth and kinds[j] == kinds[i]:
            j += 1
        runs.append(LayerRun(
            kinds[i], j - i,
            at=sum(k.moe == kinds[i].moe for k in kinds[:i]),
            cache=sum(k.full == kinds[i].full for k in kinds[:i])))
        i = j
    return tuple(runs)


@dataclasses.dataclass(frozen=True)
class LatentMoEBlock:
    """A described block other than PreNorm LayerNorm + GEGLU + learned
    positions (``TransformerConfig.block``; None is the classic block):
    RMSNorm, rotary positions, latent attention without query
    compression (a token caches ONE row a layer, ``kv_rank +
    qk_rope_dim`` wide, read materialised by prefill and absorbed by
    decode: ops/attention.py), SiLU-gated feed-forwards without biases,
    ``dense_layers`` leading dense layers and then routed-and-shared
    expert layers with dropless routing (ops/moe.py), scores scaled by
    the query/key head size. ``TransformerConfig.heads`` gives the heads;
    ``dim_head`` and ``ff_mult`` are not read."""
    kv_rank: int = 512
    qk_nope_dim: int = 128
    qk_rope_dim: int = 64
    v_head_dim: int = 128
    rope_theta: float = 1e6
    norm_eps: float = 1e-6
    dense_layers: int = 1
    dense_hidden: int = 6144
    num_experts: int = 128
    experts_per_token: int = 6
    expert_hidden: int = 768
    shared_hidden: int = 1536       # n_shared_experts x expert_hidden
    routed_scale: float = 2.448
    name: str = "latent_moe"
    embed_scale = 1.0               # token embeddings enter as they are
    first_expert = 0                # every routed expert is held here

    @property
    def experts_held(self) -> int:
        return self.num_experts

    @property
    def score_dim(self) -> int:
        """The width whose inverse root scales the scores."""
        return self.qk_nope_dim + self.qk_rope_dim

    def layer_kinds(self, depth: int) -> Tuple["LayerKind", ...]:
        return tuple(LayerKind(moe=i >= self.dense_layers, full=True)
                     for i in range(depth))

    @property
    def entry_width(self) -> int:
        """Numbers a token caches a layer: the latent and the roped key."""
        return self.kv_rank + self.qk_rope_dim

    @property
    def row_width(self) -> int:
        """Width of the row that holds them: ``entry_width`` filled up with
        zeros to whole 128-lane tiles (576 -> 640). A minor dimension that
        is not whole tiles is padded to them in device memory anyway, and
        the TPU then lays such a pool out PAGE-minor to avoid the pad:
        every program that takes the pool relays it on the way in and out
        (two pool-sized copies a chunk, 2.3 GB of temporaries at the
        published widths; PERF.md section 6, PR 27). The zeros take part
        in every contraction and add nothing."""
        return -(-self.entry_width // 128) * 128


@dataclasses.dataclass(frozen=True)
class WindowGQABlock:
    """The second described block: grouped-query attention
    (``TransformerConfig.heads`` query heads read ``kv_heads`` key/value
    heads of ``head_dim``, query head i the head ``i // (heads /
    kv_heads)``) behind RMSNorms over each query and key head, with an
    output gate (``o * sigmoid(W_g a)``); ``layer_types`` says, layer by
    layer of the layers run here, whether a layer attends the last
    ``window`` rows with rotary positions (``"sliding"``) or every earlier
    row with no position at all (``"full"``): a token caches one K and one
    V row a layer, the key/value heads side by side in it, and the page
    pool is held per layer type (serve/kv_pool.py). Four RMSNorms a layer (before and after each
    branch, the second on the branch's output before it is added).
    SiLU-gated feed-forwards without biases: ``dense_layers`` leading
    dense ones, then routed-and-shared expert layers whose router scores
    all ``num_experts`` while the chip HOLDS ``experts_held`` of them from
    ``first_expert`` on (ops/moe.py: the picks that fall on other experts
    take no part). Token embeddings enter times ``embed_scale``.
    ``dim_head`` and ``ff_mult`` of the configuration are not read."""
    kv_heads: int = 8
    head_dim: int = 128
    window: int = 4096
    layer_types: Tuple[str, ...] = ("sliding", "sliding", "sliding", "full")
    rope_theta: float = 1e4
    norm_eps: float = 1e-5
    dense_layers: int = 1
    dense_hidden: int = 12288
    num_experts: int = 256
    experts_per_token: int = 4
    expert_hidden: int = 3072
    shared_hidden: int = 3072
    routed_scale: float = 2.448
    experts_held: int = 256
    first_expert: int = 0
    embed_scale: float = 1.0
    name: str = "window_gqa_moe"

    def __post_init__(self):
        bad = set(self.layer_types) - {"sliding", "full"}
        if bad:
            raise ValueError(f"layer_types holds {sorted(bad)}: a layer is "
                             f"'sliding' or 'full'")
        if not 0 <= self.first_expert <= self.first_expert \
                + self.experts_held <= self.num_experts \
                or self.experts_held < 1:
            raise ValueError(
                f"experts {self.first_expert}..{self.first_expert} + "
                f"{self.experts_held} are not a share of "
                f"{self.num_experts}")

    @property
    def score_dim(self) -> int:
        return self.head_dim

    def layer_kinds(self, depth: int) -> Tuple[LayerKind, ...]:
        if len(self.layer_types) != depth:
            raise ValueError(f"layer_types names {len(self.layer_types)} "
                             f"layers, depth is {depth}")
        return tuple(LayerKind(moe=i >= self.dense_layers,
                               full=t == "full")
                     for i, t in enumerate(self.layer_types))

    def cache_layers(self, full: bool) -> Tuple[int, ...]:
        """The layers (indices into the layers run here) of one attention
        type, in order: their number is the depth of that type's pool."""
        return tuple(i for i, t in enumerate(self.layer_types)
                     if (t == "full") == full)

    @staticmethod
    def pool_buffers(full: bool) -> Tuple[str, str]:
        """The K and V buffers of the pool of one attention type."""
        return ("k", "v") if full else ("window_k", "window_v")

    def ring_pages(self, page_size: int, total_len: int) -> int:
        """Pages a slot holds of a window layer at most: the window and
        one more, through which it slides (a ring: the row of position p
        lies at ``p % (ring_pages * page_size)`` and overwrites a row
        that left the window at least ``page_size`` positions ago); never
        more than the whole sequence's."""
        return min(-(-self.window // page_size) + 1,
                   -(-total_len // page_size))


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    dim: int
    depth: int
    seq_len: int
    heads: int = 8
    dim_head: int = 64
    ff_mult: int = 4
    causal: bool = True
    attn_dropout: float = 0.0
    ff_dropout: float = 0.0
    reversible: bool = False
    # per-layer dense/sparse selection; bool or tuple of bools of len depth
    # (reference transformer.py:155-158 cast_tuple)
    sparse_attn: Union[bool, Tuple[bool, ...]] = False
    sparse_block: int = 16
    attn_impl: str = "xla"      # 'xla' | 'flash'
    # flash backward: 'xla' blockwise scan | 'pallas' split dq/dkv kernels
    # (causal tile skipping) | 'pallas_fused' single-pass kernel (one
    # score computation per tile pair); only meaningful with
    # attn_impl='flash'
    attn_bwd_impl: str = "xla"
    # flash kernel tile sizes (q rows x k cols per grid step); multiples of
    # the (8, 128) TPU register tile. Tunable: larger k tiles amortize the
    # per-tile softmax-stats update, larger q tiles cut grid steps
    flash_block_q: int = 128
    flash_block_k: int = 128
    sparse_impl: str = "ref"    # 'ref' | 'windowed' | 'pallas'
    # reference uses dim**-0.5 (transformer.py:57); 'head' gives dim_head**-0.5
    scale_mode: str = "dim"
    remat: str = "none"          # 'none' | 'save_ln' | 'dots' | 'full'
    # Mixture-of-Experts FF (beyond reference — SURVEY.md §2b lists EP/MoE
    # absent): 0 = plain GEGLU; >0 replaces every FF with a top-k MoE of
    # that many experts (ops.moe), expert axis shardable over 'ep'
    moe_experts: int = 0
    moe_k: int = 2
    moe_capacity: float = 1.25
    # a described block in place of the classic one
    block: Optional[Union[LatentMoEBlock, WindowGQABlock]] = None

    def __post_init__(self):
        blk = self.block
        if blk is None:
            return
        refused = {"reversible": self.reversible,
                   "sparse_attn": any(self.sparse_pattern),
                   "attn_impl='flash'": self.attn_impl != "xla",
                   "moe_experts (capacity routing)": bool(self.moe_experts),
                   "remat": self.remat != "none",
                   "dropout": bool(self.attn_dropout or self.ff_dropout),
                   "causal=False": not self.causal}
        for option, on in refused.items():
            if on:
                raise BlockOptionError(blk.name, option)
        if not 0 <= blk.dense_layers <= self.depth:
            raise ValueError(f"dense_layers {blk.dense_layers} not in "
                             f"[0, depth={self.depth}]")
        blk.layer_kinds(self.depth)      # it names every layer run here

    @property
    def moe(self):
        from dalle_pytorch_tpu.ops.moe import MoEConfig
        return MoEConfig(dim=self.dim, num_experts=self.moe_experts,
                         k=self.moe_k, ff_mult=self.ff_mult,
                         capacity_factor=self.moe_capacity)

    @property
    def sparse_pattern(self) -> Tuple[bool, ...]:
        if isinstance(self.sparse_attn, bool):
            return (self.sparse_attn,) * self.depth
        assert len(self.sparse_attn) == self.depth
        return tuple(self.sparse_attn)

    @property
    def scale(self) -> float:
        if self.block is not None:
            return self.block.score_dim ** -0.5
        base = self.dim if self.scale_mode == "dim" else self.dim_head
        return base ** -0.5


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def layer_init(key: Array, cfg: TransformerConfig, dtype=jnp.float32) -> dict:
    k_attn, k_ff1, k_ff2 = jax.random.split(key, 3)
    hidden = cfg.dim * cfg.ff_mult
    if cfg.moe_experts:
        from dalle_pytorch_tpu.ops.moe import moe_init
        ff = {"ln": core.layernorm_init(cfg.dim, dtype),
              "moe": moe_init(k_ff1, cfg.moe, dtype)}
    else:
        ff = {
            "ln": core.layernorm_init(cfg.dim, dtype),
            "w1": core.linear_init(k_ff1, cfg.dim, hidden * 2, dtype=dtype),
            "w2": core.linear_init(k_ff2, hidden, cfg.dim, dtype=dtype),
        }
    return {
        "attn": {
            "ln": core.layernorm_init(cfg.dim, dtype),
            **attn_ops.attention_init(k_attn, cfg.dim, cfg.heads, cfg.dim_head,
                                      dtype),
        },
        "ff": ff,
    }


def block_layer_init(key: Array, cfg: TransformerConfig, kind: LayerKind,
                     dtype=jnp.float32) -> dict:
    """One layer of a described block: dense or routed feed-forward. The
    window-and-full block's layers carry a second norm a branch, on the
    branch's output."""
    from dalle_pytorch_tpu.ops.moe import dropless_init
    blk = cfg.block
    k_attn, k_ff = jax.random.split(key)
    ff = dropless_init(k_ff, cfg.dim, blk, dtype) if kind.moe \
        else core.swiglu_init(k_ff, cfg.dim, blk.dense_hidden, dtype)
    norm = {"ln": core.rmsnorm_init(cfg.dim, dtype)}
    if isinstance(blk, WindowGQABlock):
        norm["post_ln"] = core.rmsnorm_init(cfg.dim, dtype)
        attn = attn_ops.gqa_init(k_attn, cfg.dim, cfg.heads, blk, dtype)
    else:
        attn = attn_ops.latent_init(k_attn, cfg.dim, cfg.heads, blk, dtype)
    return {"attn": {**norm, **attn}, "ff": {**norm, **ff}}


def transformer_init(key: Array, cfg: TransformerConfig,
                     dtype=jnp.float32) -> dict:
    """Layer parameters stacked on a leading depth axis. A described
    block's stack is not uniform: its leading dense layers and its expert
    layers are two subtrees, ``{"dense": ..., "moe": ...}``, each stacked
    over its own layers (``block_stack`` scans one after the other)."""
    if cfg.block is not None:
        k_dense, k_moe = jax.random.split(key)
        n_dense = cfg.block.dense_layers
        # a layer's attention type changes no parameter's shape
        return {
            "dense": jax.vmap(lambda k: block_layer_init(
                k, cfg, LayerKind(False, True), dtype))(
                    jax.random.split(k_dense, n_dense)),
            "moe": jax.vmap(lambda k: block_layer_init(
                k, cfg, LayerKind(True, True), dtype))(
                    jax.random.split(k_moe, cfg.depth - n_dense)),
        }
    keys = jax.random.split(key, cfg.depth)
    return jax.vmap(lambda k: layer_init(k, cfg, dtype))(keys)


def is_block_params(params: dict) -> bool:
    """Whether a transformer subtree is a described block's two stacks."""
    return "dense" in params and "moe" in params


def block_name_of(params: dict) -> str:
    """Which described block a transformer subtree holds, for a caller
    that has parameters and no configuration to name in its refusal."""
    return WindowGQABlock.name if "gate" in params["moe"]["attn"] \
        else LatentMoEBlock.name


# ---------------------------------------------------------------------------
# the two residual branches (f = attention, g = feed-forward)
# ---------------------------------------------------------------------------

def _maybe_remat(body, mode: str):
    """Wrap a scanned layer body per the remat mode. 'full' recomputes the
    whole body in the backward (max memory savings, ~1/3 more FLOPs);
    'dots' keeps matmul outputs saved and recomputes only the vector work
    (layernorm/gelu/elementwise — near-zero extra MXU FLOPs, ~2/3 of the
    saved-activation bytes reclaimed); 'save_ln' is the surgical variant:
    save EVERYTHING except the two tagged f32 layernorm intermediates per
    block (core.layernorm's checkpoint_names) — the cheapest possible
    recompute (a layernorm each) for the bytes that actually drive OOM
    (8 f32 saves/layer dominate the flash stack's activation
    footprint)."""
    if mode == "full":
        return jax.checkpoint(body)
    if mode == "dots":
        return jax.checkpoint(
            body, policy=jax.checkpoint_policies.dots_saveable)
    if mode == "save_ln":
        return jax.checkpoint(
            body,
            policy=jax.checkpoint_policies.save_anything_except_these_names(
                "ln_f32_in", "ln_f32_out"))
    if mode != "none":
        raise ValueError(f"remat must be 'none', 'dots', 'full' or "
                         f"'save_ln', got {mode!r}")
    return body


def attn_branch(layer_params: dict, x: Array, mask: Optional[Array],
                cfg: TransformerConfig, is_sparse, key: Optional[Array],
                train: bool) -> Array:
    """PreNorm attention. ``is_sparse`` is a static python bool when the
    caller resolved the dense/sparse choice at trace time (the periodic-
    pattern scan below), or a traced bool scalar — then both branches are
    compiled once and selected per layer with lax.cond."""
    p = layer_params["attn"]
    h = core.layernorm(p["ln"], x)

    dense_kwargs = dict(heads=cfg.heads, dim_head=cfg.dim_head,
                        scale=cfg.scale, causal=cfg.causal, mask=mask,
                        dropout_rate=cfg.attn_dropout, dropout_key=key,
                        train=train, impl=cfg.attn_impl,
                        bwd_impl=cfg.attn_bwd_impl,
                        block_q=cfg.flash_block_q,
                        block_k=cfg.flash_block_k)

    pattern = cfg.sparse_pattern
    if not any(pattern):
        return attn_ops.attention_apply(p, h, **dense_kwargs)

    def dense_fn(h):
        return attn_ops.attention_apply(p, h, **dense_kwargs)

    def sparse_fn(h):
        # Pad to a block multiple, mask pad keys, slice back — the reference's
        # SparseAttention padding contract (transformer.py:109-135).
        n = h.shape[1]
        block = cfg.sparse_block
        pad = (-n) % block
        kp_mask = mask
        if pad:
            h = jnp.pad(h, ((0, 0), (0, pad), (0, 0)))
            if kp_mask is None:
                kp_mask = jnp.ones((h.shape[0], n), bool)
            kp_mask = jnp.pad(kp_mask, ((0, 0), (0, pad)))
        q, k, v = attn_ops.qkv_project(p, h, cfg.heads)
        if cfg.sparse_impl == "pallas":
            from dalle_pytorch_tpu.ops.block_sparse import block_sparse_attention
            out = block_sparse_attention(q, k, v, scale=cfg.scale,
                                         causal=cfg.causal, mask=kp_mask,
                                         block=block)
        elif cfg.sparse_impl == "windowed":
            out = sparse.sparse_attention_windowed(
                q, k, v, scale=cfg.scale, causal=cfg.causal, mask=kp_mask,
                block=block)
        elif cfg.sparse_impl == "ref":
            out = sparse.sparse_attention_ref(q, k, v, scale=cfg.scale,
                                             causal=cfg.causal, mask=kp_mask,
                                             block=block)
        else:
            raise ValueError(f"unknown sparse impl {cfg.sparse_impl!r}; "
                             f"expected 'ref', 'windowed', or 'pallas'")
        out = out[:, :, :n]          # drop pad rows before the tail matmul
        return attn_ops.output_tail(p, out, dropout_rate=cfg.attn_dropout,
                                    dropout_key=key, train=train)

    if all(pattern):
        return sparse_fn(h)
    if isinstance(is_sparse, bool):           # statically resolved per layer
        return sparse_fn(h) if is_sparse else dense_fn(h)
    return lax.cond(is_sparse, sparse_fn, dense_fn, h)


@jax.named_scope("ff")
def ff_branch(layer_params: dict, x: Array, cfg: TransformerConfig,
              key: Optional[Array], train: bool,
              dropout_fn=None) -> Array:
    """PreNorm GEGLU feed-forward (reference transformer.py:33-49).
    ``dropout_fn(key, h)`` overrides the default whole-tensor dropout —
    the sequence-parallel stack passes a positional variant so the mask
    is invariant to sequence sharding."""
    p = layer_params["ff"]
    h = core.layernorm(p["ln"], x)
    h = core.linear(p["w1"], h)
    h, gates = jnp.split(h, 2, axis=-1)
    h = h * core.gelu(gates)
    h = (dropout_fn(key, h) if dropout_fn is not None
         else core.dropout(key, h, cfg.ff_dropout, train))
    return core.linear(p["w2"], h)


def ff_or_moe(layer_params: dict, x: Array, cfg: TransformerConfig,
              key: Optional[Array], train: bool) -> Tuple[Array, Array]:
    """FF residual branch -> (out, aux). Plain GEGLU returns aux = 0; the
    MoE variant returns its load-balance loss (the scan accumulates it)."""
    if cfg.moe_experts:
        from dalle_pytorch_tpu.ops.moe import moe_apply
        with jax.named_scope("ff"):
            p = layer_params["ff"]
            h = core.layernorm(p["ln"], x)
            out, aux = moe_apply(p["moe"], h, cfg=cfg.moe)
            return core.dropout(key, out, cfg.ff_dropout, train), aux
    return (ff_branch(layer_params, x, cfg, key, train),
            jnp.float32(0.0))


# ---------------------------------------------------------------------------
# the described blocks: their branches, written once
# ---------------------------------------------------------------------------
#
# ``transformer_apply``, ``ops.decode.prefill`` and the paged gather decode
# step all run a layer as: norm, the block's projections, a READ, the
# block's output projection, residual, then ``block_ff``, residual. Only
# the read differs (materialised over a whole sequence here and in prefill,
# over the page pool in decode), and it is handed in.

def block_layer(lp: dict, h: Array, positions: Array, read, cfg,
                run: LayerRun):
    """One layer of the run ``run``. ``read(attn_params, query, entry) ->
    o`` is the attention read, where ``query`` and ``entry`` are what the
    block's projection gives: (q_nope, q_rope) and the latent row for the
    latent block; q and the (k, v) rows for the window-and-full block.
    -> (h, (entry, load)): the row(s) to cache and the routed layer's
    load (zeros for a dense layer)."""
    blk = cfg.block
    p = lp["attn"]
    hn = core.rmsnorm(p["ln"], h, eps=blk.norm_eps)
    if isinstance(blk, WindowGQABlock):
        q, gate, entry = attn_ops.gqa_project(
            p, hn, positions, cfg.heads, blk, rotary=not run.full)
        a = attn_ops.gqa_out(p, read(p, q, entry), gate)
        a = core.rmsnorm(p["post_ln"], a, eps=blk.norm_eps)
    else:
        q_nope, q_rope, entry = attn_ops.latent_project(p, hn, positions,
                                                        cfg.heads, blk)
        a = attn_ops.latent_out(p, read(p, (q_nope, q_rope), entry))
    h = h + a
    f, load = block_ff(lp["ff"], h, blk, run.moe)
    return h + f, (entry, load)


def block_ff(p: dict, x: Array, blk, moe: bool):
    """Feed-forward branch of a described block behind its norm(s) ->
    (out, load: ops.moe.dropless_apply's, zeros for a dense layer)."""
    from dalle_pytorch_tpu.ops.moe import dropless_apply, load_width
    hn = core.rmsnorm(p["ln"], x, eps=blk.norm_eps)
    if moe:
        out, load = dropless_apply(p, hn, blk)
    else:
        with jax.named_scope("ff"):
            out = core.swiglu(p, hn)
        load = jnp.zeros((load_width(blk),), jnp.int32)
    if "post_ln" in p:
        out = core.rmsnorm(p["post_ln"], out, eps=blk.norm_eps)
    return out, load


def block_stack(params: dict, h: Array, layer_fn, cfg):
    """The non-uniform stack: one scan a run of layers alike in both kinds
    (``layer_runs``), in the published order. ``layer_fn(lp, h, layer,
    run) -> (h, out)`` with ``layer`` the traced index into the pool of
    the run's attention type (``run.cache`` + the index in the run) and
    ``run`` static. -> (h, outs stacked over the whole depth)."""
    def scan_layers(h, run: LayerRun):
        sub = params["moe" if run.moe else "dense"]
        whole = None
        if run.moe:
            # the routed experts are not scanned: a layer reads them out
            # of the whole stack by its index (ops.moe.dropless_experts)
            whole = sub["ff"]["experts"]
            sub = {**sub, "ff": {k: v for k, v in sub["ff"].items()
                                 if k != "experts"}}
        whole_stack = run.count == jax.tree.leaves(sub)[0].shape[0]

        def body(h, xs):
            lp, local = xs
            if not whole_stack:
                # a run that is part of its stack indexes the stack
                # itself: a slice of it handed to the scan is a copy of
                # the run's weights, every step
                lp = jax.tree.map(lambda a: lax.dynamic_index_in_dim(
                    a, run.at + local, keepdims=False), sub)
            if whole is not None:
                lp = {**lp, "ff": {**lp["ff"], "experts": {
                    **whole, "layer": run.at + local}}}
            return layer_fn(lp, h, run.cache + local, run)

        return lax.scan(body, h, (sub if whole_stack else None,
                                  jnp.arange(run.count)))

    outs = []
    for run in layer_runs(cfg.block, cfg.depth):
        h, out = scan_layers(h, run)
        outs.append(out)
    return h, jax.tree.map(lambda *a: jnp.concatenate(a), *outs)


def block_apply_full(params: dict, x: Array, cfg: TransformerConfig,
                     mask: Optional[Array] = None):
    """A described block over whole sequences x (b, n, dim) at positions
    0..n-1, causal (and windowed on a sliding layer), the MATERIALISED
    read: the full forward and the prefill are this one function. ->
    (h (b, n, dim), entries stacked over the depth: every layer's rows to
    cache, (depth, b, n, row_width) or the pair of (depth, b, n, kv_heads,
    head_dim) K and V; loads (depth, load width))."""
    blk = cfg.block
    n = x.shape[1]
    positions = jnp.arange(n)
    with jax.named_scope("attn.read"):       # the masks
        allowed = jnp.tril(jnp.ones((n, n), bool))[None, None]
        if mask is not None:
            allowed = allowed & (mask[:, None, :, None]
                                 & mask[:, None, None, :])
        if isinstance(blk, WindowGQABlock):
            near = (positions[:, None] - positions[None, :]) < blk.window
            in_window = allowed & near[None, None]

    def layer_fn(lp, h, _layer, run):
        def read(p, query, entry):
            if isinstance(blk, WindowGQABlock):
                return attn_ops.gqa_attend_materialised(
                    query, *entry, allowed if run.full else in_window,
                    cfg.scale, window=not run.full)
            return attn_ops.latent_attend_materialised(
                p, *query, entry, allowed, blk, cfg.scale)
        return block_layer(lp, h, positions, read, cfg, run)

    h, (entries, loads) = block_stack(params, x, layer_fn, cfg)
    return h, entries, loads


# ---------------------------------------------------------------------------
# apply
# ---------------------------------------------------------------------------

# largest dense/sparse pattern period the scan body statically unrolls;
# longer (aperiodic) patterns fall back to the traced lax.cond selection
_MAX_UNROLL_PERIOD = 4


def _pattern_period(pattern: Tuple[bool, ...]) -> int:
    """Smallest p with pattern == pattern[:p] * (len/p)."""
    depth = len(pattern)
    for p in range(1, depth + 1):
        if depth % p == 0 and pattern == pattern[:p] * (depth // p):
            return p
    return depth


def unrolled_layout(params, keys, pattern):
    """(stacked params, stacked keys, one period of the pattern) when the
    dense/sparse pattern is periodic enough to unroll statically, else None.

    Shared dispatch for both execution engines (sequential scan here,
    reversible custom_vjp in ops.reversible): layer stacks reshape from
    (depth, ...) to (depth/period, period, ...) so the scan body unrolls the
    period with the dense/sparse choice resolved at trace time."""
    period = _pattern_period(pattern)
    if period > _MAX_UNROLL_PERIOD:
        return None
    nsteps = len(pattern) // period
    stacked = jax.tree.map(
        lambda a: a.reshape(nsteps, period, *a.shape[1:]), params)
    keys_r = keys.reshape(nsteps, period, *keys.shape[1:])
    return stacked, keys_r, tuple(pattern[:period])


def _layer_keys(rng: Optional[Array], depth: int) -> Array:
    if rng is None:
        # Only reached when dropout is statically off (apply validates) —
        # the keys are dead values threaded through scan for pytree symmetry.
        rng = jax.random.PRNGKey(0)
    # A (depth, 2) split shape works for both typed keys and legacy uint32
    # keys (the latter gain a trailing (2,) data axis).
    return jax.random.split(rng, (depth, 2))


def transformer_apply(params: dict, x: Array, *, cfg: TransformerConfig,
                      mask: Optional[Array] = None,
                      rng: Optional[Array] = None,
                      train: bool = False,
                      with_aux: bool = False):
    """Run the stack. x: (b, n, dim); mask: (b, n) bool (True = keep).
    ``with_aux=True`` returns (x, aux) where aux is the summed MoE
    load-balance loss over the depth (0.0 for plain GEGLU stacks)."""
    if train and rng is None and (cfg.attn_dropout > 0 or cfg.ff_dropout > 0):
        raise ValueError(
            "transformer_apply(train=True) with nonzero dropout requires an "
            "explicit `rng` key — JAX has no global RNG state to fall back on")

    if cfg.block is not None:
        if train:
            raise BlockOptionError(cfg.block.name, "train=True",
                                   "the block is served, not trained")
        out, _, _ = block_apply_full(params, x, cfg, mask)
        return (out, jnp.float32(0.0)) if with_aux else out

    if cfg.reversible:
        if cfg.moe_experts:
            raise ValueError("reversible=True does not compose with MoE "
                             "layers (the FF branch is not invertible-"
                             "stream shaped); use the sequential engine")
        from dalle_pytorch_tpu.ops.reversible import reversible_apply
        out = reversible_apply(params, x, cfg=cfg, mask=mask, rng=rng,
                               train=train)
        return (out, jnp.float32(0.0)) if with_aux else out

    keys = _layer_keys(rng, cfg.depth)
    pattern = cfg.sparse_pattern
    layout = unrolled_layout(params, keys, pattern)
    # The MoE aux is collected as a scan OUTPUT (summed after), not a
    # carry: under shard_map the per-layer aux can be varying over mesh
    # axes the zero init isn't, and outputs have no carry-type constraint
    # (carries would need a pcast this module can't know the axes for).

    if layout is not None:
        # Periodic dense/sparse patterns (the reference's (True, False)*32,
        # transformer.py:155-158, has period 2) resolve STATICALLY — no
        # lax.cond at all. A differentiated cond between a Pallas
        # custom_vjp branch and a dense branch inside a 64-step scan is
        # brutal on XLA/Mosaic compile time; this path keeps one compiled
        # super-layer regardless of depth.
        stacked, keys_r, period_pat = layout

        def body(h, xs):
            lp, lkeys = xs
            aux = jnp.float32(0.0)
            for i, is_sparse in enumerate(period_pat):
                lpi = jax.tree.map(lambda a: a[i], lp)
                h = h + attn_branch(lpi, h, mask, cfg, bool(is_sparse),
                                    lkeys[i][0], train)
                f, a = ff_or_moe(lpi, h, cfg, lkeys[i][1], train)
                h = h + f
                aux = aux + a
            return h, aux

        body = _maybe_remat(body, cfg.remat)
        out, auxs = lax.scan(body, x, (stacked, keys_r))
        return (out, auxs.sum()) if with_aux else out

    sparse_flags = jnp.asarray(pattern)

    def body(h, xs):
        lp, lkeys, is_sparse = xs
        h = h + attn_branch(lp, h, mask, cfg, is_sparse, lkeys[0], train)
        f, a = ff_or_moe(lp, h, cfg, lkeys[1], train)
        return h + f, a

    body = _maybe_remat(body, cfg.remat)
    out, auxs = lax.scan(body, x, (params, keys, sparse_flags))
    return (out, auxs.sum()) if with_aux else out

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
from dalle_pytorch_tpu.ops import deltanet as delta_ops
from dalle_pytorch_tpu.ops import shortconv as conv_ops
from dalle_pytorch_tpu.ops import ssm as ssm_ops

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
    """The ways in which a described block's layers differ: a dense or a
    routed feed-forward; attention over every earlier row (``full``) or
    over a window of them; and the ``mixer``: ``"attn"`` (attends its own
    keys and values and caches them), ``"cross"`` (attends the rows that
    an earlier full layer cached, and caches nothing), ``"ssm"`` (a
    state-space layer: a recurrent state a slot, no rows), ``"conv"`` (a
    gated short convolution: its tail a slot, no rows), ``"delta"`` (a
    gated delta-rule layer: a matrix state a value head and the
    convolution's tail a slot, no rows) or ``"gmu"`` (reads an earlier
    state-space layer's output of the same token, and caches nothing)."""
    moe: bool
    full: bool
    mixer: str = "attn"

    @property
    def pool(self) -> Optional[str]:
        """The cache this layer reads: ``"full"``, ``"window"``,
        ``"state"`` or None."""
        if self.mixer in ("ssm", "conv", "delta"):
            return "state"
        if self.mixer == "gmu":
            return None
        return "full" if self.full else "window"

    @property
    def stores(self) -> bool:
        """Whether the layer writes the cache it reads."""
        return self.mixer in ("attn", "ssm", "conv", "delta")


@dataclasses.dataclass(frozen=True)
class LayerRun:
    """``count`` layers of one kind scanned as one (``block_stack``):
    consecutive ones, or every ``period``-th of a block whose stack
    repeats a pattern of several kinds. ``at`` is the first one's index
    in its parameter stack (``stack_of``) and ``cache`` its layer index in
    the cache it reads (``LayerKind.pool``): its own layer's in the pool
    it stores to or, for a layer that stores nothing, the layer's whose
    rows it reads."""
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


def stack_scans(blk, depth: int) -> Tuple[Tuple[LayerRun, ...], ...]:
    """The stack as the scans that run it, in the published order: each a
    pattern of ``blk.period`` kinds (one ``LayerRun`` a member) repeated
    ``count`` times. At period 1 these are the runs of layers alike; a
    deeper cut or a whole model is the same code with more scans."""
    kinds = blk.layer_kinds(depth)
    scans, i = [], 0
    while i < depth:
        pattern = kinds[i:i + blk.period]
        p, count = len(pattern), 1
        while kinds[i + count * p:i + (count + 1) * p] == pattern:
            count += 1
        stacks = [blk.stack_of(k) for k in pattern]
        pools = [k.pool for k in pattern if k.stores]
        if len(set(stacks)) < p or len(set(pools)) < len(pools):
            raise ValueError(
                f"{blk.name}: a scanned pattern of layers {i}..{i + p} "
                f"holds two of one parameter stack or of one pool")
        scans.append(tuple(LayerRun(
            kind, count,
            at=sum(blk.stack_of(k) == blk.stack_of(kind)
                   for k in kinds[:i + j]),
            cache=_cache_index(kinds, i + j))
            for j, kind in enumerate(pattern)))
        i += count * p
    return tuple(scans)


def _cache_index(kinds, i: int) -> int:
    stored = sum(k.stores and k.pool == kinds[i].pool for k in kinds[:i])
    return stored if kinds[i].stores else max(stored - 1, 0)


def layer_runs(blk, depth: int) -> Tuple[LayerRun, ...]:
    """Every ``LayerRun`` of ``stack_scans``, in order."""
    return tuple(run for scan in stack_scans(blk, depth) for run in scan)


class DescribedBlock:
    """What the stack, the caches and the engine ask of a described block
    beyond its own fields; each block overrides what differs."""
    period = 1      # kinds in the pattern that the stack repeats
    tied_head = False   # an output head of its own (models/dalle.py)
    layer_norms = False     # RMSNorms (a gain alone), not LayerNorms
    sink = False    # no learned logit beside a window softmax's rows
    route_eps = 0.0     # nothing beside the picked scores' sum (ops/moe.py)
    route_scores = "sigmoid"    # the router's scores, with a selection bias
    shared_gate = False     # the shared unit's output enters as it is
    state_mixer = "ssm"     # the recurrent mixer whose state a slot carries

    @staticmethod
    def stack_of(kind: LayerKind) -> str:
        """The parameter stack that holds a layer of ``kind``."""
        return "moe" if kind.moe else "dense"

    def pool_buffers(self, pool: str) -> Tuple[str, ...]:
        """The buffers of one cache: a page pool's K and V, or what
        ``state_layout`` names."""
        if pool == "state":
            return tuple(self.state_layout(0))
        return {"full": ("k", "v"), "window": ("window_k", "window_v")}[pool]

    def state_layout(self, dim: int) -> dict:
        """What a slot carries a layer of the ``"state"`` cache, buffer by
        buffer: ``{name: (shape, bytes an
        element; None = the pool's float type)}`` at a stream ``dim``
        wide (serve/kv_pool.py ``page_layout``). A block without
        recurrent layers holds none."""
        return {}

    def cache_layers(self, pool: str, depth: int) -> Tuple[int, ...]:
        """The layers (indices into the layers run here) that STORE to one
        cache, in order: their number is the depth of its buffers."""
        return tuple(i for i, k in enumerate(self.layer_kinds(depth))
                     if k.stores and k.pool == pool)

    def pools(self, depth: int) -> dict:
        """``{cache: its buffers}`` of the caches that the block holds."""
        return {pool: self.pool_buffers(pool)
                for pool in ("full", "window", "state")
                if self.cache_layers(pool, depth)}

    def carried(self, x: Array) -> dict:
        """What a layer hands to later layers inside one pass over the
        stack, beside the residual stream (``block_layer``'s ``shared``),
        as zeros shaped after the stream ``x``."""
        return {}

    def buffer_row_width(self, name: str) -> int:
        """Numbers in one row of the page-pool buffer ``name``
        (``pool_buffers``): a width a BUFFER (serve/kv_pool.py
        ``page_layout``). One width serves every buffer of a block whose
        cached rows are all alike."""
        return self.page_row_width

    def ring_pages(self, page_size: int, total_len: int) -> int:
        """Pages a slot holds of a window layer at most: the window and
        one more, through which it slides (a ring: the row of position p
        lies at ``p % (ring_pages * page_size)`` and overwrites a row
        that left the window at least ``page_size`` positions ago); never
        more than the whole sequence's."""
        return min(-(-self.window // page_size) + 1,
                   -(-total_len // page_size))


@dataclasses.dataclass(frozen=True)
class LatentMoEBlock(DescribedBlock):
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

    @staticmethod
    def mixer_of(kind: LayerKind) -> str:
        return "latent"

    @staticmethod
    def pool_buffers(pool: str) -> Tuple[str, ...]:
        return ("latent",)          # one row a token: no V

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
    def page_row_width(self) -> int:
        return self.row_width

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


def _check_share_and_rotary(blk) -> None:
    """What a block that holds a share of its experts and turns a part
    of a head owes its fields."""
    if not 0 <= blk.first_expert <= blk.first_expert + blk.experts_held \
            <= blk.num_experts or blk.experts_held < 1:
        raise ValueError(
            f"experts {blk.first_expert}..{blk.first_expert} + "
            f"{blk.experts_held} are not a share of {blk.num_experts}")
    turned = blk.rotary_dim or blk.head_dim
    if turned % 2 or not 0 < turned <= blk.head_dim:
        raise ValueError(f"rotary_dim {turned}: an even number of a "
                         f"head's {blk.head_dim}")


@dataclasses.dataclass(frozen=True)
class WindowGQABlock(DescribedBlock):
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
    ``dim_head`` and ``ff_mult`` of the configuration are not read.

    The fields from ``full_kv_heads`` on describe window-and-full
    configurations whose two layer types differ in more than the mask;
    their defaults are the block above, unchanged:

      * ``full_kv_heads``: key/value heads of a FULL layer (None: as many
        as a window layer's ``kv_heads``). Where they differ a layer
        type's K and V projections differ in shape, so the full layers
        lie in parameter stacks of their own (``stack_of``), and the two
        pools' rows differ in width (``buffer_row_width``);
      * ``v_head_dim``: a value head's numbers (None: ``head_dim``, which
        queries and keys always have): a K row is then wider than a V row;
      * ``rotary_dim``: the leading numbers of a query or key head that
        rotary positions turn (None: all ``head_dim``);
      * ``full_rope_theta``: the rotary base of a full layer (None: a
        full layer carries no position);
      * ``value_scale``: what the projected values are multiplied by;
      * ``qk_norm`` / ``out_gate`` / ``sandwich_norms``: whether a layer
        has the norms over a query and a key head, the output gate, and
        the second norm a branch;
      * ``sink``: a window layer's softmax holds one learned logit a
        query head beside its rows' (it joins the denominator and takes
        no value, so a row's weights sum to less than 1);
      * ``shared_hidden`` 0: a routed layer without a shared expert."""
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
    full_kv_heads: Optional[int] = None
    v_head_dim: Optional[int] = None
    rotary_dim: Optional[int] = None
    full_rope_theta: Optional[float] = None
    value_scale: float = 1.0
    qk_norm: bool = True
    out_gate: bool = True
    sandwich_norms: bool = True
    sink: bool = False

    def __post_init__(self):
        bad = set(self.layer_types) - {"sliding", "full"}
        if bad:
            raise ValueError(f"layer_types holds {sorted(bad)}: a layer is "
                             f"'sliding' or 'full'")
        _check_share_and_rotary(self)

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

    @staticmethod
    def mixer_of(kind: LayerKind) -> str:
        return "gqa"

    def kv_heads_of(self, full: bool) -> int:
        """Key/value heads of a layer of one type."""
        return self.full_kv_heads if full and self.full_kv_heads \
            else self.kv_heads

    def rope_theta_of(self, full: bool) -> Optional[float]:
        """The rotary base of a layer of one type; None: no position."""
        return self.full_rope_theta if full else self.rope_theta

    @property
    def types_differ(self) -> bool:
        """Whether a full layer's parameters differ in shape from a
        window layer's (its key/value heads, or the sink it lacks)."""
        return self.sink or self.kv_heads_of(True) != self.kv_heads

    def stack_of(self, kind: LayerKind) -> str:
        """``"dense"`` / ``"moe"``; where the layer types differ in shape
        the full layers' stacks go by ``"dense_full"`` / ``"moe_full"``."""
        name = "moe" if kind.moe else "dense"
        return name + "_full" if kind.full and self.types_differ else name

    def carried(self, x: Array) -> dict:
        """Of a block with a sink: the weight that the sinks of the window
        layers so far took, summed over them and the query heads, a
        number a token of ``x`` (a counter, ops/decode.py
        ``decode_step_block``; no layer reads it)."""
        if not self.sink:
            return {}
        return {"sink_mass": jnp.zeros(x.shape[:-1], jnp.float32)}

    def buffer_row_width(self, name: str) -> int:
        """A cached row of one buffer, every key/value head's numbers side
        by side: the key/value heads of its layer type (``window_*``: the
        window layers') times a key head's numbers (``*k``) or a value
        head's."""
        heads = self.kv_heads_of(not name.startswith("window_"))
        return heads * (self.head_dim if name.endswith("k")
                        else self.v_head_dim or self.head_dim)


MIXER_NAMES = ("ssm", "window", "full", "cross", "gmu")


@dataclasses.dataclass(frozen=True)
class SSMHybridBlock(DescribedBlock):
    """The third described block: a strictly alternating stack of
    recurrent and attention mixers, each layer ``h = x + Mixer(LN(x))``,
    ``y = h + MLP(LN(h))`` with LayerNorms (gain and bias) and a dense
    SiLU-gated feed-forward without biases. ``mixers`` names each layer's:

      * ``"ssm"``: a selective state-space layer (ops/ssm.py). A slot
        carries a fixed-size state and the convolution's tail, no rows;
      * ``"window"`` / ``"full"``: differential attention
        (ops/attention.py) over the last ``window`` rows / over every
        earlier row: ``TransformerConfig.heads`` query heads over
        ``kv_heads`` key/value heads of ``head_dim``, in pairs. A token
        caches one K and one V row a layer, the key/value heads side by
        side in it, in the pool of its layer type (serve/kv_pool.py);
      * ``"cross"``: the same attention with a query projection alone,
        over the rows that the nearest earlier ``"full"`` layer cached (and
        that layer's row of the current token): it projects no key or
        value and caches nothing;
      * ``"gmu"``: a gated memory unit over the scan output that the
        nearest earlier ``"ssm"`` layer gave for the same token: no state
        of its own.

    No position enters anywhere (the state-space layers carry order).
    The stack is scanned in periods of two layers (``stack_scans``).
    ``dim_head`` and ``ff_mult`` of the configuration are not read."""
    mixers: Tuple[str, ...] = ("ssm", "window", "ssm", "full", "gmu",
                               "cross")
    kv_heads: int = 20
    head_dim: int = 64
    window: int = 512
    d_inner: int = 5120
    d_state: int = 16
    d_conv: int = 4
    dt_rank: int = 160
    dense_hidden: int = 10240
    norm_eps: float = 1e-5
    name: str = "ssm_hybrid"
    period = 2
    tied_head = True                # logits against the embedding rows
    layer_norms = True              # a gain and a bias
    embed_scale = 1.0
    num_experts = 0                 # every feed-forward is dense

    def __post_init__(self):
        bad = set(self.mixers) - set(MIXER_NAMES)
        if bad:
            raise ValueError(f"mixers holds {sorted(bad)}: a layer is one "
                             f"of {MIXER_NAMES}")
        for reader, source in (("gmu", "ssm"), ("cross", "full")):
            if reader in self.mixers and source not in self.mixers[
                    :self.mixers.index(reader)]:
                raise ValueError(f"a {reader!r} layer reads an earlier "
                                 f"{source!r} layer, and none comes first")
        if self.kv_heads % 2:
            raise ValueError(f"kv_heads {self.kv_heads}: differential "
                             f"attention pairs the key/value heads up")

    @property
    def dense_layers(self) -> int:
        return len(self.mixers)

    @property
    def score_dim(self) -> int:
        return self.head_dim

    @property
    def page_row_width(self) -> int:
        return self.kv_heads * self.head_dim

    def layer_kinds(self, depth: int) -> Tuple[LayerKind, ...]:
        if len(self.mixers) != depth:
            raise ValueError(f"mixers names {len(self.mixers)} layers, "
                             f"depth is {depth}")
        return tuple(LayerKind(
            moe=False, full=m in ("full", "cross"),
            mixer=m if m in ("ssm", "cross", "gmu") else "attn")
            for m in self.mixers)

    @staticmethod
    def stack_of(kind: LayerKind) -> str:
        return kind.mixer       # "attn": window and full layers alike

    @staticmethod
    def mixer_of(kind: LayerKind) -> str:
        return "diff" if kind.mixer == "attn" else kind.mixer

    def state_layout(self, dim: int) -> dict:
        """The recurrent state, float32 whatever the pool's type, with
        the wide dimension minor (whole lanes), and the convolution's
        tail."""
        return {"ssm_state": ((self.d_state, self.d_inner), 4),
                "ssm_conv": ((self.d_conv - 1, self.d_inner), None)}

    def carried(self, x: Array) -> dict:
        """The last state-space layer's scan output and the full layer's
        K and V rows, of the tokens in ``x``."""
        lead = x.shape[:-1]
        rows = jnp.zeros(lead + (self.kv_heads, self.head_dim), x.dtype)
        return {"m": jnp.zeros(lead + (self.d_inner,), x.dtype),
                "k": rows, "v": rows}

    @staticmethod
    def lam_init(layer) -> Array:
        """Differential attention's constant of layer ``layer``."""
        return 0.8 - 0.6 * jnp.exp(-0.3 * jnp.asarray(layer, jnp.float32))


@dataclasses.dataclass(frozen=True)
class ShortConvGQABlock(DescribedBlock):
    """The fourth described block: ``layer_types`` says, layer by layer of
    the layers run here, whether a layer's mixer is a gated short
    convolution (``"conv"``, ops/shortconv.py: a slot carries the last
    ``conv_taps - 1`` gated inputs, ``dim`` wide, and caches no row) or
    grouped-query attention over every earlier row (``"full"``:
    ``TransformerConfig.heads`` query heads read ``kv_heads`` key/value
    heads of ``head_dim``, RMSNorms over each query and key head, then
    rotary positions on the whole head at ``rope_theta``; no gate, no
    bias; a token caches one K and one V row a layer in the one page
    pool). Two RMSNorms a layer, one before each branch. SiLU-gated
    feed-forwards without biases: ``dense_layers`` leading dense ones,
    then layers of ``num_experts`` routed experts, ALL held here, the
    picked sigmoid scores over (their sum + ``route_eps``), no shared
    expert. The head is the embedding rows behind a final RMSNorm; the
    rotary positions of the full layers are the only positions.

    The stack runs at period 1 (``stack_scans``: runs of layers alike),
    whatever the published ratio of the two layer types: a scanned
    period of it would hold several layers of one parameter stack.
    ``dim_head`` and ``ff_mult`` of the configuration are not read.

    What ``ops.attention.gqa_*`` and ``ops.moe`` read of a block beyond
    the fields is fixed here (the class constants): the combination that
    this block is, and no other, is what its tests hold."""
    layer_types: Tuple[str, ...] = ("conv", "full", "conv", "conv", "conv")
    kv_heads: int = 8
    head_dim: int = 64
    conv_taps: int = 3
    rope_theta: float = 1e6
    norm_eps: float = 1e-5
    dense_layers: int = 1
    dense_hidden: int = 11776
    num_experts: int = 64
    experts_per_token: int = 4
    expert_hidden: int = 1536
    routed_scale: float = 1.0
    route_eps: float = 1e-6
    name: str = "shortconv_gqa_moe"
    tied_head = True                # logits against the embedding rows
    state_mixer = "conv"
    embed_scale = 1.0
    first_expert = 0                # every routed expert is held here
    shared_hidden = 0               # no shared expert
    qk_norm = True
    out_gate = False
    sandwich_norms = False          # no second norm a branch
    value_scale = 1.0
    v_head_dim = None               # a value head is a key head's size
    rotary_dim = None               # the whole head turns

    def __post_init__(self):
        bad = set(self.layer_types) - {"conv", "full"}
        if bad:
            raise ValueError(f"layer_types holds {sorted(bad)}: a layer is "
                             f"'conv' or 'full'")
        if self.conv_taps < 2 or self.head_dim % 2:
            raise ValueError(f"conv_taps {self.conv_taps} leaves no tail, "
                             f"or head_dim {self.head_dim} no rotary pairs")

    @property
    def experts_held(self) -> int:
        return self.num_experts

    @property
    def score_dim(self) -> int:
        return self.head_dim

    @property
    def page_row_width(self) -> int:
        return self.kv_heads * self.head_dim

    def layer_kinds(self, depth: int) -> Tuple[LayerKind, ...]:
        if len(self.layer_types) != depth:
            raise ValueError(f"layer_types names {len(self.layer_types)} "
                             f"layers, depth is {depth}")
        return tuple(LayerKind(moe=i >= self.dense_layers, full=t == "full",
                               mixer="attn" if t == "full" else "conv")
                     for i, t in enumerate(self.layer_types))

    @staticmethod
    def mixer_of(kind: LayerKind) -> str:
        return "gqa" if kind.mixer == "attn" else "conv"

    @staticmethod
    def stack_of(kind: LayerKind) -> str:
        """``"dense"`` / ``"moe"`` hold the short-convolution layers,
        ``"dense_full"`` / ``"moe_full"`` the attention layers."""
        return ("moe" if kind.moe else "dense") + (
            "_full" if kind.full else "")

    def state_layout(self, dim: int) -> dict:
        """ONE buffer: the convolution's tail, in the pool's type."""
        return {"conv_tail": ((self.conv_taps - 1, dim), None)}

    def kv_heads_of(self, full: bool) -> int:
        return self.kv_heads

    def rope_theta_of(self, full: bool) -> float:
        return self.rope_theta


@dataclasses.dataclass(frozen=True)
class DeltaGQABlock(DescribedBlock):
    """The fifth described block: ``layer_types`` says, layer by layer of
    the layers run here, whether a layer's mixer is a gated delta-rule
    layer (``"delta"``, ops/deltanet.py: ``key_heads`` key heads of
    ``key_head_dim`` under ``value_heads`` value heads of
    ``value_head_dim``; a slot carries a float32 matrix state a value head
    and the last ``conv_taps - 1`` inputs of its convolution, and caches
    no row) or gated grouped-query attention over every earlier row
    (``"full"``: ``TransformerConfig.heads`` query heads read ``kv_heads``
    key/value heads of ``head_dim``, RMSNorms over each query and key
    head, rotary positions on a head's first ``rotary_dim`` numbers at
    ``rope_theta``, an output gate ``o * sigmoid(W_g x)``, no bias; a
    token caches one K and one V row a layer in the one page pool). Two
    RMSNorms a layer, one before each branch. EVERY layer's feed-forward
    is routed: ``num_experts`` SiLU-gated experts scored by a softmax
    over all of them in float32, the ``experts_per_token`` largest picked
    (no selection bias), their scores over their sum, of which the chip
    HOLDS ``experts_held`` from ``first_expert`` on (ops/moe.py: the picks
    that fall on other experts take no part); beside them a shared unit
    of ``shared_hidden`` whose output enters times ``sigmoid(w_s . x)``.
    An untied head behind a final RMSNorm; the rotary positions of the
    full layers are the only positions.

    The stack runs at period 1 (``stack_scans``: runs of layers alike),
    whatever the published ratio of the two layer types. ``dim_head`` and
    ``ff_mult`` of the configuration are not read.

    What ``ops.attention.gqa_*`` and ``ops.moe`` read of a block beyond
    the fields is fixed here (the class constants): the combination that
    this block is, and no other, is what its tests hold."""
    layer_types: Tuple[str, ...] = ("delta", "delta", "delta", "full")
    kv_heads: int = 2
    head_dim: int = 256
    rotary_dim: Optional[int] = 64
    rope_theta: float = 1e7
    norm_eps: float = 1e-6
    key_heads: int = 16
    value_heads: int = 32
    key_head_dim: int = 128
    value_head_dim: int = 128
    conv_taps: int = 4
    num_experts: int = 512
    experts_per_token: int = 10
    expert_hidden: int = 512
    shared_hidden: int = 512
    experts_held: int = 512
    first_expert: int = 0
    name: str = "delta_gqa_moe"
    state_mixer = "delta"
    route_scores = "softmax"        # no selection bias, nothing beside the sum
    shared_gate = True              # sigmoid(w_s . x) on the shared unit
    routed_scale = 1.0
    dense_layers = 0                # every layer is routed
    dense_hidden = 0
    embed_scale = 1.0
    qk_norm = True
    out_gate = True
    sandwich_norms = False          # no second norm a branch
    value_scale = 1.0
    v_head_dim = None               # a value head is a key head's size

    def __post_init__(self):
        bad = set(self.layer_types) - {"delta", "full"}
        if bad:
            raise ValueError(f"layer_types holds {sorted(bad)}: a layer is "
                             f"'delta' or 'full'")
        _check_share_and_rotary(self)
        if self.conv_taps < 2 or self.value_heads % self.key_heads:
            raise ValueError(
                f"conv_taps {self.conv_taps} leaves no tail, or the "
                f"{self.value_heads} value heads are no multiple of the "
                f"{self.key_heads} key heads")
        if self.norm_eps != delta_ops.NORM_EPS:
            raise ValueError(
                f"norm_eps {self.norm_eps}: the delta-rule layer's gated "
                f"norm is written at {delta_ops.NORM_EPS}")

    @property
    def score_dim(self) -> int:
        return self.head_dim

    @property
    def page_row_width(self) -> int:
        return self.kv_heads * self.head_dim

    def layer_kinds(self, depth: int) -> Tuple[LayerKind, ...]:
        if len(self.layer_types) != depth:
            raise ValueError(f"layer_types names {len(self.layer_types)} "
                             f"layers, depth is {depth}")
        return tuple(LayerKind(moe=True, full=t == "full",
                               mixer="attn" if t == "full" else "delta")
                     for t in self.layer_types)

    @staticmethod
    def mixer_of(kind: LayerKind) -> str:
        return "gqa" if kind.mixer == "attn" else "delta"

    @staticmethod
    def stack_of(kind: LayerKind) -> str:
        """``"moe"`` holds the delta-rule layers, ``"moe_full"`` the
        attention layers."""
        return "moe_full" if kind.full else "moe"

    def state_layout(self, dim: int) -> dict:
        """The matrix state of every value head, float32 whatever the
        pool's type (the minor dimension whole lanes at the published
        sizes), and the convolution's tail, in the pool's type."""
        return {"delta_state": ((self.value_heads, self.key_head_dim,
                                 self.value_head_dim), 4),
                "delta_conv": ((self.conv_taps - 1,
                                2 * self.key_heads * self.key_head_dim
                                + self.value_heads * self.value_head_dim),
                               None)}

    def kv_heads_of(self, full: bool) -> int:
        return self.kv_heads

    def rope_theta_of(self, full: bool) -> float:
        return self.rope_theta


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
    block: Optional[Union[LatentMoEBlock, WindowGQABlock, SSMHybridBlock,
                          ShortConvGQABlock, DeltaGQABlock]] = None

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
        blk.layer_kinds(self.depth)      # it names every layer run here
        if not 0 <= blk.dense_layers <= self.depth:
            raise ValueError(f"dense_layers {blk.dense_layers} not in "
                             f"[0, depth={self.depth}]")

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
                     dtype=jnp.float32, layer: int = 0) -> dict:
    """One layer of a described block: its mixer under ``"attn"`` and its
    dense or routed feed-forward under ``"ff"``, each with its norm. The
    window-and-full block's layers carry a second norm a branch, on the
    branch's output; the state-space hybrid's norms are LayerNorms and its
    differential layers hold the constant of their own index ``layer``."""
    from dalle_pytorch_tpu.ops.moe import dropless_init
    blk = cfg.block
    k_attn, k_ff = jax.random.split(key)
    ff = dropless_init(k_ff, cfg.dim, blk, dtype) if kind.moe \
        else core.swiglu_init(k_ff, cfg.dim, blk.dense_hidden, dtype)
    mixer = blk.mixer_of(kind)
    norm = {"ln": (core.layernorm_init if blk.layer_norms
                   else core.rmsnorm_init)(cfg.dim, dtype)}
    if mixer == "gqa":
        if blk.sandwich_norms:
            norm["post_ln"] = core.rmsnorm_init(cfg.dim, dtype)
        attn = attn_ops.gqa_init(k_attn, cfg.dim, cfg.heads, blk, dtype,
                                 full=kind.full)
    elif mixer == "latent":
        attn = attn_ops.latent_init(k_attn, cfg.dim, cfg.heads, blk, dtype)
    elif mixer == "ssm":
        attn = ssm_ops.ssm_init(k_attn, cfg.dim, blk, dtype)
    elif mixer == "gmu":
        attn = ssm_ops.gmu_init(k_attn, cfg.dim, blk, dtype)
    elif mixer == "conv":
        attn = conv_ops.shortconv_init(k_attn, cfg.dim, blk, dtype)
    elif mixer == "delta":
        attn = delta_ops.delta_init(k_attn, cfg.dim, blk, dtype)
    else:
        attn = attn_ops.diff_init(k_attn, cfg.dim, cfg.heads, blk,
                                  blk.lam_init(layer), dtype,
                                  own_kv=mixer == "diff")
    return {"attn": {**norm, **attn}, "ff": {**norm, **ff}}


def transformer_init(key: Array, cfg: TransformerConfig,
                     dtype=jnp.float32) -> dict:
    """Layer parameters stacked on a leading depth axis. A described
    block's stack is not uniform: it is one subtree a parameter stack
    (``DescribedBlock.stack_of``: ``{"dense": ..., "moe": ...}``, or a
    subtree a kind of mixer), each stacked over its own layers in the
    published order (``block_stack`` scans them)."""
    blk = cfg.block
    if blk is not None:
        kinds = blk.layer_kinds(cfg.depth)
        names = list(dict.fromkeys(blk.stack_of(k) for k in kinds))
        out = {}
        for name, k_stack in zip(names, jax.random.split(key, len(names))):
            layers = [i for i, k in enumerate(kinds)
                      if blk.stack_of(k) == name]
            # within a stack a layer's kind changes no parameter's shape
            out[name] = jax.vmap(lambda k, i: block_layer_init(
                k, cfg, kinds[layers[0]], dtype, layer=i))(
                    jax.random.split(k_stack, len(layers)),
                    jnp.asarray(layers))
        return out
    keys = jax.random.split(key, cfg.depth)
    return jax.vmap(lambda k: layer_init(k, cfg, dtype))(keys)


def is_block_params(params: dict) -> bool:
    """Whether a transformer subtree is a described block's stacks (and
    not the classic block's one stack of layers, each a mixer and a
    feed-forward)."""
    return "ff" not in params


def block_name_of(params: dict) -> str:
    """Which described block a transformer subtree holds, for a caller
    that has parameters and no configuration to name in its refusal."""
    if not any(name.startswith(("dense", "moe")) for name in params):
        return SSMHybridBlock.name      # ``stack_of``: stacks by mixer
    if any("a_log" in stack["attn"] and "ba" in stack["attn"]
           for stack in params.values()):
        return DeltaGQABlock.name
    if any("conv" in stack["attn"] for stack in params.values()):
        return ShortConvGQABlock.name
    attn = next(iter(params.values()))["attn"]
    return LatentMoEBlock.name if "k_up" in attn else WindowGQABlock.name


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

def block_norm(p: dict, x: Array, blk) -> Array:
    """The norm its parameters describe (a gain with a shift is a
    LayerNorm, a gain alone an RMSNorm) at the block's epsilon."""
    if "b" in p:
        return core.layernorm(p, x, eps=blk.norm_eps)
    return core.rmsnorm(p, x, eps=blk.norm_eps)


# A mixer: (p, hn, positions, read, shared, cfg, run) -> (the branch's
# output, the entry to cache or None, ``shared`` as later layers get it).

def _mix_latent(p, hn, positions, read, shared, cfg, run):
    q_nope, q_rope, entry = attn_ops.latent_project(p, hn, positions,
                                                    cfg.heads, cfg.block)
    a = attn_ops.latent_out(p, read(p, (q_nope, q_rope), entry))
    return a, entry, shared


def _mix_gqa(p, hn, positions, read, shared, cfg, run):
    blk = cfg.block
    q, gate, entry = attn_ops.gqa_project(
        p, hn, positions, cfg.heads, blk, full=run.full)
    o = read(p, q, entry)
    if "sink" in p:
        # a window layer's read gives the sink's weight beside its output
        o, mass = o
        shared = {**shared, "sink_mass": shared["sink_mass"] + mass}
    a = attn_ops.gqa_out(p, o, gate)
    if "post_ln" in p:
        a = core.rmsnorm(p["post_ln"], a, eps=blk.norm_eps)
    return a, entry, shared


def _mix_ssm(p, hn, positions, read, shared, cfg, run):
    out, m, state = read(p, hn, None)
    return out, state, {**shared, "m": m}


def _mix_conv(p, hn, positions, read, shared, cfg, run):
    """A short convolution (-> out, the new tail) or a delta-rule layer
    (-> out, the new state and tail): what it carries is all it hands
    on."""
    out, carried = read(p, hn, None)
    return out, carried, shared


def _mix_gmu(p, hn, positions, read, shared, cfg, run):
    return ssm_ops.gmu(p, hn, shared["m"]), None, shared


def _mix_diff(p, hn, positions, read, shared, cfg, run):
    """A window or full differential layer (its own K and V, which a full
    layer also hands on) or a cross layer (the handed-on ones)."""
    blk = cfg.block
    q, entry = attn_ops.diff_project(p, hn, cfg.heads, blk)
    if entry is None:
        o = read(p, q, (shared["k"], shared["v"]))
    else:
        o = read(p, q, entry)
        if run.full:
            shared = {**shared, "k": entry[0], "v": entry[1]}
    return attn_ops.diff_out(p, o, blk.norm_eps), entry, shared


_MIXERS = {"latent": _mix_latent, "gqa": _mix_gqa, "ssm": _mix_ssm,
           "conv": _mix_conv, "delta": _mix_conv, "gmu": _mix_gmu,
           "diff": _mix_diff, "cross": _mix_diff}


def state_scope(blk):
    """A state pool's reads and stores in a trace: its block's recurrent
    mixer's scope."""
    if blk.state_mixer == "delta":
        return jax.named_scope("delta.rule")
    return jax.named_scope("conv.mix") if blk.state_mixer == "conv" \
        else jax.named_scope("ssm.scan")


# a recurrent mixer's two forms, by ``LayerKind.mixer``: over whole
# sequences (p, x, mask) and one token against what its slot carries
# (p, x, the carried buffers: one alone, or the tuple of several)
STATE_SEQUENCE = {"ssm": ssm_ops.ssm_sequence,
                  "conv": conv_ops.shortconv_sequence,
                  "delta": delta_ops.delta_sequence}
STATE_STEP = {"ssm": ssm_ops.ssm_step, "conv": conv_ops.shortconv_step,
              "delta": delta_ops.delta_step}


def block_layer(lp: dict, h: Array, shared: dict, positions: Array, read,
                cfg, run: LayerRun):
    """One layer of the run ``run``: norm, the mixer, residual, norm, the
    feed-forward, residual. ``read(mixer_params, query, entry) -> o`` is
    the layer's read of its cache, where ``query`` and ``entry`` are what
    the block's projection gives: (q_nope, q_rope) and the latent row for
    the latent block; q and the (k, v) rows for the grouped-query and the
    differential layers; the normed input and None for a state-space
    layer, whose read is the whole recurrence (-> out, m, the new state),
    for a short convolution (-> out, the new tail) and for a delta-rule
    layer (-> out, the new state and tail).
    ``shared`` is what earlier layers handed on (``DescribedBlock
    .carried``). -> (h, shared, (entry, load)): what to cache (None for a
    layer that caches nothing) and the routed layer's load (zeros for a
    dense layer)."""
    blk = cfg.block
    p = lp["attn"]
    hn = block_norm(p["ln"], h, blk)
    a, entry, shared = _MIXERS[blk.mixer_of(run.kind)](
        p, hn, positions, read, shared, cfg, run)
    h = h + a
    f, load = block_ff(lp["ff"], h, blk, run.moe)
    return h + f, shared, (entry, load)


def block_ff(p: dict, x: Array, blk, moe: bool):
    """Feed-forward branch of a described block behind its norm(s) ->
    (out, load: ops.moe.dropless_apply's, zeros for a dense layer)."""
    from dalle_pytorch_tpu.ops.moe import dropless_apply, load_width
    hn = block_norm(p["ln"], x, blk)
    if moe:
        out, load = dropless_apply(p, hn, blk)
    else:
        with jax.named_scope("ff"):
            out = core.swiglu(p, hn)
        load = jnp.zeros((load_width(blk),), jnp.int32)
    if "post_ln" in p:
        out = core.rmsnorm(p["post_ln"], out, eps=blk.norm_eps)
    return out, load


def block_stack(params: dict, h: Array, layer_fn, cfg, span=None):
    """The non-uniform stack: one scan a pattern of layers repeated
    (``stack_scans``: at period 1 a run of layers alike), in the published
    order. ``layer_fn(lp, h, shared, layer, run) -> (h, shared, (entry,
    load))`` with ``layer`` the traced index into the cache that the run
    reads (``run.cache`` + the index in the run) and ``run`` static. ->
    (h, entries: ``{buffer: what the layers that store to it gave, stacked
    over them}`` for every buffer of ``blk.pools``, loads stacked over the
    whole depth, ``shared`` as the last layer left it).

    ``span`` ``(first, stop, around)`` runs the scans ``first`` up to
    ``stop`` as ONE function of a static ``choice``, which ``around``
    calls (under a ``lax.switch``: a decode step's width profile,
    ops/decode.py ``decode_step_block``); their layers get
    ``layer_fn(..., choice)``, and what comes out of ``around`` is the
    stream, what the layers share, and those scans' entries and loads."""
    blk = cfg.block

    def member(run: LayerRun):
        sub = params[blk.stack_of(run.kind)]
        whole = None
        if run.moe:
            # the routed experts are not scanned: a layer reads them out
            # of the whole stack by its index (ops.moe.dropless_experts)
            whole = sub["ff"]["experts"]
            sub = {**sub, "ff": {k: v for k, v in sub["ff"].items()
                                 if k != "experts"}}
        whole_stack = run.count == jax.tree.leaves(sub)[0].shape[0]
        return sub, whole, whole_stack

    def scan_pattern(carry, scan, layer_fn):
        members = [member(run) for run in scan]

        def body(carry, xs):
            h, shared = carry
            lps, local = xs
            outs = []
            for run, (sub, whole, whole_stack), lp in zip(scan, members,
                                                          lps):
                if not whole_stack:
                    # a run that is part of its stack indexes the stack
                    # itself: a slice of it handed to the scan is a copy
                    # of the run's weights, every step
                    lp = jax.tree.map(lambda a: lax.dynamic_index_in_dim(
                        a, run.at + local, keepdims=False), sub)
                if whole is not None:
                    lp = {**lp, "ff": {**lp["ff"], "experts": {
                        **whole, "layer": run.at + local}}}
                # a layer that stores nothing reads ONE earlier layer's
                # rows, whichever of the run's layers it is
                layer = run.cache + local if run.kind.stores else run.cache
                h, shared, out = layer_fn(lp, h, shared, layer, run)
                outs.append(out)
            return (h, shared), tuple(outs)

        return lax.scan(body, carry, (
            tuple(sub if whole_stack else None
                  for sub, _, whole_stack in members),
            jnp.arange(scan[0].count)))

    def joined(parts):
        return parts[0] if len(parts) == 1 else jax.tree.map(
            lambda *a: jnp.concatenate(a), *parts)

    def scan_all(carry, scans, layer_fn):
        outs = []
        for scan in scans:
            carry, out = scan_pattern(carry, scan, layer_fn)
            outs.append(out)
        return carry, outs

    scans = stack_scans(blk, cfg.depth)
    first, stop, around = span or (len(scans), len(scans), None)
    carry, outs = scan_all((h, blk.carried(h)), scans[:first], layer_fn)
    if stop > first:
        carry, inside = around(lambda choice: scan_all(
            carry, scans[first:stop],
            lambda *layer: layer_fn(*layer, choice)))
        carry, after = scan_all(carry, scans[stop:], layer_fn)
        outs += inside + after
    stored, loads = {}, []
    for scan, out in zip(scans, outs):
        for run, (entry, load) in zip(scan, out):
            loads.append(load)
            if run.kind.stores:
                stored.setdefault(run.kind.pool, []).append(entry)
    entries = {}
    for pool, parts in stored.items():
        rows = joined(parts)
        names = blk.pool_buffers(pool)
        entries.update(zip(names, rows if len(names) > 1 else (rows,)))
    return carry[0], entries, joined(loads), carry[1]


def block_apply_full(params: dict, x: Array, cfg: TransformerConfig,
                     mask: Optional[Array] = None,
                     lens: Optional[Array] = None):
    """A described block over whole sequences x (b, n, dim) at positions
    0..n-1, causal (and windowed on a window layer), the MATERIALISED
    read: the full forward and the prefill are this one function.
    ``lens`` (b,) is each row's own length where the rows are padded on
    the right to n: a recurrent state stops there (attention needs no
    telling: a row never attends what comes after it). -> (h (b, n, dim),
    entries: every buffer's rows to cache stacked over the layers that
    store to it (``block_stack``): (layers, b, n, row_width), or (layers,
    b, n, kv_heads, head_dim) K and V, or a state-space layer's (layers,
    b, d_state, d_inner) and (layers, b, d_conv - 1, d_inner), or a short
    convolution's tail (layers, b, conv_taps - 1, dim), or a delta-rule
    layer's (layers, b, value_heads, dk, dv) and (layers, b, conv_taps -
    1, its convolution's width), after each row's last position; loads
    (depth, load width))."""
    blk = cfg.block
    n = x.shape[1]
    positions = jnp.arange(n)
    pools = blk.pools(cfg.depth)
    with jax.named_scope("attn.read"):       # the masks
        allowed = jnp.tril(jnp.ones((n, n), bool))[None, None]
        if mask is not None:
            allowed = allowed & (mask[:, None, :, None]
                                 & mask[:, None, None, :])
        if "window" in pools:
            near = (positions[:, None] - positions[None, :]) < blk.window
            in_window = allowed & near[None, None]
    if "state" in pools:
        with state_scope(blk):
            advance = mask
            if lens is not None:
                within = positions[None, :] < lens[:, None]
                advance = within if mask is None else mask & within

    def layer_fn(lp, h, shared, _layer, run):
        def read(p, query, entry):
            if run.kind.pool == "state":
                return STATE_SEQUENCE[run.kind.mixer](p, query, advance)
            if blk.mixer_of(run.kind) == "latent":
                return attn_ops.latent_attend_materialised(
                    p, *query, entry, allowed, blk, cfg.scale)
            return attn_ops.gqa_attend_materialised(
                query, *entry, allowed if run.full else in_window,
                cfg.scale, window=not run.full,
                diff_lam=attn_ops.diff_lambda(p) if "lam" in p else None,
                sink=p.get("sink"))
        return block_layer(lp, h, shared, positions, read, cfg, run)

    return block_stack(params, x, layer_fn, cfg)[:3]


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

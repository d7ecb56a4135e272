"""DALLE — joint text+image autoregressive transformer, TPU-native.

Capability parity with the reference DALLE (reference
dalle_pytorch/dalle_pytorch.py:241-407):

  * vocab layout ``[0, num_text_tokens) text | [.., +num_image_tokens) image
    | last = EOS`` (reference :277,303-315,403);
  * per-position logits mask: positions < text_seq_len-1 predict text ids
    only, positions >= text_seq_len-1 predict image ids only, EOS only at
    the final position (reference :303-315) — mask row i governs the token
    PREDICTED there, i.e. token i+1;
  * the image embedding is TIED to the VAE codebook (reference :283).  In
    this functional design DALLE *owns* the table: ``dalle_init`` seeds
    ``params['image_emb']`` from the VAE codebook, DALLE training updates it,
    and ``generate_images`` decodes through the VAE convs with DALLE's copy
    (``models.vae.decode(codebook=...)``) — same semantics as the reference's
    shared module, explicit instead of aliased;
  * axial image position embedding.  Default factorizes over the real token
    grid; ``axial_compat='full_image'`` reproduces the reference quirk of a
    (image_size × image_size) table of which only the first image_seq_len
    rows are used (reference :268, SURVEY.md §5 "axial pos-emb quirk");
  * training loss: one CE over all positions, labels = [text, image+offset]
    shifted left with EOS appended (reference :398-406);
  * ``generate_images``: top-k (keep (1-thres)·vocab) then temperature
    categorical (reference :41-47,339-341) — but as ONE jit-compiled
    ``lax.scan`` with an on-device KV cache (ops.decode) instead of a python
    loop of full re-forwards, including the text-completion mode genDALLE
    exercises by passing a short unpadded prompt (reference genDALLE.py:106).
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from dalle_pytorch_tpu.models import vae as vae_mod
from dalle_pytorch_tpu.ops import core, decode as decode_ops
from dalle_pytorch_tpu.ops import transformer as T

Array = jax.Array


@dataclasses.dataclass(frozen=True)
class DALLEConfig:
    dim: int
    depth: int
    vae: vae_mod.VAEConfig
    num_text_tokens: int = 10000
    text_seq_len: int = 256
    heads: int = 8
    dim_head: int = 64
    reversible: bool = False
    attn_dropout: float = 0.0
    ff_dropout: float = 0.0
    sparse_attn: Union[bool, Tuple[bool, ...]] = False
    sparse_block: int = 16
    attn_impl: str = "xla"
    # flash backward: 'xla' | 'pallas' (split) | 'pallas_fused' kernels
    attn_bwd_impl: str = "xla"
    flash_block_q: int = 128     # flash kernel tile sizes (transformer cfg)
    flash_block_k: int = 128
    sparse_impl: str = "ref"
    # MoE FF (beyond reference): 0 = plain GEGLU; >0 experts per layer,
    # expert axis shardable over 'ep'. aux coef weights the Switch
    # load-balance loss into the training objective.
    moe_experts: int = 0
    moe_k: int = 2
    moe_aux_coef: float = 1e-2
    scale_mode: str = "dim"     # reference transformer.py:57 uses dim**-0.5
    remat: str = "none"
    # 'grid' factorizes over the token grid; 'full_image' reproduces the
    # reference's (image_size, image_size) table quirk.
    axial_compat: str = "grid"
    # CE memory strategy: 0 computes the loss over the full (b, seq,
    # total_tokens) logits; a positive value streams the head+CE over
    # sequence chunks of that size under jax.checkpoint, so peak logits
    # memory is (b, chunk, total_tokens) — the 12k-vocab head over seq 1280
    # is otherwise the largest train-time buffer. Same loss, bitwise-close
    # grads; one extra head matmul on the backward pass.
    loss_chunk: int = 0
    # a described block in place of PreNorm LayerNorm + GEGLU + learned
    # and axial positions (ops.transformer.LatentMoEBlock): rotary
    # positions inside the block, so no position tables; an untied,
    # bias-free head behind an RMSNorm. Served, not trained.
    block: Optional[Union[T.LatentMoEBlock, T.WindowGQABlock,
                          T.SSMHybridBlock, T.ShortConvGQABlock,
                          T.DeltaGQABlock]] = None

    @property
    def image_seq_len(self) -> int:
        return self.vae.image_seq_len

    @property
    def num_image_tokens(self) -> int:
        return self.vae.num_tokens

    @property
    def seq_len(self) -> int:
        return self.text_seq_len + self.image_seq_len

    @property
    def total_tokens(self) -> int:
        return self.num_text_tokens + self.num_image_tokens + 1  # + EOS

    @property
    def eos_token_id(self) -> int:
        return self.total_tokens - 1

    @property
    def transformer(self) -> T.TransformerConfig:
        return T.TransformerConfig(
            dim=self.dim, depth=self.depth, seq_len=self.seq_len,
            heads=self.heads, dim_head=self.dim_head, causal=True,
            attn_dropout=self.attn_dropout, ff_dropout=self.ff_dropout,
            reversible=self.reversible, sparse_attn=self.sparse_attn,
            sparse_block=self.sparse_block, attn_impl=self.attn_impl,
            attn_bwd_impl=self.attn_bwd_impl,
            flash_block_q=self.flash_block_q,
            flash_block_k=self.flash_block_k,
            sparse_impl=self.sparse_impl, scale_mode=self.scale_mode,
            remat=self.remat, moe_experts=self.moe_experts,
            moe_k=self.moe_k, block=self.block)


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def dalle_init(key: Array, cfg: DALLEConfig,
               vae_params: Optional[dict] = None,
               dtype=jnp.float32) -> dict:
    """Parameter pytree. ``vae_params`` seeds the tied image embedding from
    the VAE codebook (reference dalle_pytorch.py:283; requires
    vae.codebook_dim == dim, as the tie implies)."""
    ks = jax.random.split(key, 6)
    g = cfg.vae.grid_size

    if cfg.axial_compat == "full_image":
        ax_rows, ax_cols = cfg.vae.image_size, cfg.vae.image_size
    elif cfg.axial_compat == "grid":
        ax_rows, ax_cols = g, g
    else:
        raise ValueError(f"unknown axial_compat {cfg.axial_compat!r}")

    if vae_params is not None:
        if cfg.vae.codebook_dim != cfg.dim:
            raise ValueError(
                "tied codebook requires vae.codebook_dim == dalle dim "
                f"({cfg.vae.codebook_dim} != {cfg.dim})")
        image_emb = {"w": vae_params["codebook"]["w"].astype(dtype)}
    else:
        image_emb = core.embedding_init(ks[1], cfg.num_image_tokens, cfg.dim,
                                        dtype)

    params = {
        "text_emb": core.embedding_init(ks[0], cfg.num_text_tokens, cfg.dim,
                                        dtype),
        "image_emb": image_emb,
        "transformer": T.transformer_init(ks[5], cfg.transformer, dtype),
    }
    k_head = jax.random.fold_in(ks[5], 1)
    if cfg.block is not None and cfg.block.tied_head:
        # no position table; the head is the embedding rows themselves
        # behind the block's kind of norm (``to_logits``): text rows,
        # image rows, and the EOS row, which is never an input
        params["eos_emb"] = core.embedding_init(k_head, 1, cfg.dim, dtype)
        params["to_logits"] = {"ln": (
            core.layernorm_init if cfg.block.layer_norms
            else core.rmsnorm_init)(cfg.dim, dtype)}
        return params
    if cfg.block is not None:
        # positions are the block's own (rotary): no tables; an untied,
        # bias-free head behind an RMSNorm
        params["to_logits"] = {
            "ln": core.rmsnorm_init(cfg.dim, dtype),
            "proj": core.linear_init(k_head, cfg.dim, cfg.total_tokens,
                                     bias=False, dtype=dtype)}
        return params
    params["text_pos_emb"] = core.embedding_init(ks[2], cfg.text_seq_len,
                                                 cfg.dim, dtype)
    params["image_pos_emb"] = {
        "rows": core.normal_init(ks[3], (ax_rows, cfg.dim), 1.0, dtype),
        "cols": core.normal_init(ks[4], (ax_cols, cfg.dim), 1.0, dtype),
    }
    params["to_logits"] = {
        "ln": core.layernorm_init(cfg.dim, dtype),
        "proj": core.linear_init(k_head, cfg.dim, cfg.total_tokens,
                                 dtype=dtype),
    }
    return params


# ---------------------------------------------------------------------------
# embeddings / masks
# ---------------------------------------------------------------------------

@jax.named_scope("embed")
def image_pos_emb(params: dict, cfg: DALLEConfig, positions: Array) -> Array:
    """Summed-axial position embedding for flat image positions
    (0..image_seq_len). 'grid' maps n -> (n // g, n % g); 'full_image' maps
    over the image_size-wide table exactly as the reference's
    AxialPositionalEmbedding(axial_shape=(image_size, image_size)) does."""
    p = params["image_pos_emb"]
    width = p["cols"].shape[0]
    rows = jnp.take(p["rows"], positions // width, axis=0)
    cols = jnp.take(p["cols"], positions % width, axis=0)
    return rows + cols


def logits_mask_rows(cfg: DALLEConfig, rows: Array) -> Array:
    """Rows ``rows`` (any shape, int) of ``logits_mask`` -> rows.shape +
    (total_tokens,), computed from the positions: a per-slot sampler reads
    its slots' rows and never builds the (seq_len, total_tokens) table,
    which a compiler rebuilds inside the decode loop (0.56 GB a step at a
    4352 x 128256 table)."""
    n, t = cfg.seq_len, cfg.total_tokens
    seq = jnp.asarray(rows)[..., None]
    logit = jnp.arange(t)
    text_boundary = cfg.text_seq_len - 1
    return (
        ((seq >= text_boundary) & (logit < cfg.num_text_tokens))
        | ((seq < text_boundary) & (logit >= cfg.num_text_tokens))
        | ((seq != (n - 1)) & (logit >= (t - 1)))
    )


def logits_mask(cfg: DALLEConfig) -> Array:
    """(seq_len, total_tokens) bool, True = FORBIDDEN (fill with -max), the
    reference's buffer (dalle_pytorch.py:303-315)."""
    return logits_mask_rows(cfg, jnp.arange(cfg.seq_len))


@jax.named_scope("embed")
def embed_prompt(params: dict, cfg: DALLEConfig, text: Array,
                 image_ids: Optional[Array] = None) -> Array:
    """Token embeddings for [text (b, t)] ++ [image ids (b, n_img)]."""
    b, t = text.shape
    learned = cfg.block is None      # else the block's own, or none
    tok = jnp.take(params["text_emb"]["w"], text, axis=0)
    if learned:
        tok = tok + params["text_pos_emb"]["w"][None, :t]
    if image_ids is not None and image_ids.shape[1] > 0:
        n_img = image_ids.shape[1]
        img = jnp.take(params["image_emb"]["w"], image_ids, axis=0)
        if learned:
            img = img + image_pos_emb(params, cfg, jnp.arange(n_img))[None]
        tok = jnp.concatenate([tok, img], axis=1)
    return _block_embed_scale(cfg, tok)


def _block_embed_scale(cfg: DALLEConfig, tok: Array) -> Array:
    """A described block may take its token embeddings times a constant
    (``embed_scale``: the square root of the width, where the model was
    parameterised so)."""
    if cfg.block is None or cfg.block.embed_scale == 1.0:
        return tok
    return tok * jnp.asarray(cfg.block.embed_scale, tok.dtype)


@jax.named_scope("embed")
def decode_token_embed(params: dict, cfg: DALLEConfig, cur_tok: Array,
                       pos: Array) -> Array:
    """Embedding of the token(s) fed at position(s) ``pos`` during KV-cache
    decoding — the ONE definition shared by ``generate_images``'s scan and
    the serve engine's slot-batched step (serve/engine.py), so the two
    samplers cannot diverge. ``cur_tok`` (b,) ids (image ids WITHOUT the
    text-vocab offset); ``pos`` a traced scalar or a (b,) per-slot vector.
    Ids are clipped into each table so the off-branch gather of the
    ``where`` select stays in range."""
    pos = jnp.asarray(pos)
    text_e = jnp.take(params["text_emb"]["w"],
                      jnp.clip(cur_tok, 0, cfg.num_text_tokens - 1), axis=0)
    img_e = jnp.take(params["image_emb"]["w"],
                     jnp.clip(cur_tok, 0, cfg.num_image_tokens - 1), axis=0)
    if cfg.block is None:            # else rotary, inside the block
        text_e = text_e + jnp.take(
            params["text_pos_emb"]["w"],
            jnp.clip(pos, 0, cfg.text_seq_len - 1), axis=0)
        img_pos = jnp.clip(pos - cfg.text_seq_len, 0, cfg.image_seq_len - 1)
        img_e = img_e + image_pos_emb(params, cfg, img_pos)
    is_text = pos < cfg.text_seq_len
    if pos.ndim:
        is_text = is_text[:, None]
    return _block_embed_scale(cfg, jnp.where(is_text, text_e, img_e))


@jax.named_scope("head")
def to_logits(params: dict, h: Array,
              cfg: Optional[DALLEConfig] = None) -> Array:
    """The head behind its norm; a described block's configuration says
    the norm's epsilon (without one it is the norm's default). A head
    without a projection of its own is TIED: the logits are ``h`` against
    the text, image and EOS embedding rows themselves, in the order of
    the vocabulary (the rows are not held twice)."""
    ln = params["to_logits"]["ln"]
    if cfg is not None and cfg.block is not None:
        h = T.block_norm(ln, h, cfg.block)
    else:
        h = core.norm(ln, h)
    if "proj" in params["to_logits"]:
        return core.linear(params["to_logits"]["proj"], h)
    return jnp.concatenate(
        [jnp.einsum("...d,vd->...v", h, params[name]["w"].astype(h.dtype))
         for name in ("text_emb", "image_emb", "eos_emb")], axis=-1)


def draft_transformer_config(tcfg: T.TransformerConfig,
                             d: int) -> T.TransformerConfig:
    """The shallow draft model's config for speculative decode: the
    first ``d`` layers of the target transformer, everything else
    unchanged. ``sparse_attn`` must be re-sliced explicitly because
    ``sparse_pattern`` is derived from depth — a bare depth override
    would re-broadcast a bool or fail the tuple-length assert."""
    if not 1 <= d <= tcfg.depth:
        raise ValueError(
            f"draft depth must be in [1, {tcfg.depth}], got {d}")
    return dataclasses.replace(
        tcfg, depth=d, sparse_attn=tuple(tcfg.sparse_pattern[:d]))


def draft_transformer_params(params: dict, d: int) -> dict:
    """The draft head's weights: the leading-``d`` slice of every
    stacked transformer leaf. An early exit, not a separate model — the
    draft shares the target's weights (and, at the call site, the SAME
    ``to_logits`` head and sampler), so no extra memory and no training.
    Cheap under jit (a slice of resident buffers, no copy); call it
    INSIDE the traced decode fn so hot-swapped weights stay live."""
    return jax.tree.map(lambda a: a[:d], params)


def quantize_for_decode(params: dict) -> dict:
    """Int8-quantize the weight-heavy inference path — the transformer
    linears and the vocab head (ops.quant docstring has the bandwidth
    arithmetic). Embedding tables, positional/axial tables, layernorms,
    and the tied codebook stay in their stored dtype: they are gathered
    or tiny, and the VAE decode needs the codebook as-is. Inference only
    (no tangent through int8); quantize after restore, never checkpoint
    the result."""
    from dalle_pytorch_tpu.ops import quant
    if T.is_block_params(params["transformer"]):
        raise T.BlockOptionError(T.block_name_of(params["transformer"]),
                                 "--quantize int8*")
    out = dict(params)
    out["transformer"] = quant.quantize_tree_int8(params["transformer"])
    out["to_logits"] = quant.quantize_tree_int8(params["to_logits"])
    return out


# ---------------------------------------------------------------------------
# forward / loss
# ---------------------------------------------------------------------------

def dalle_apply(params: dict, text: Array, image=None, *, cfg: DALLEConfig,
                mask: Optional[Array] = None,
                vae_params: Optional[dict] = None,
                rng: Optional[Array] = None, train: bool = False,
                return_loss: bool = False):
    """Forward (reference DALLE.forward, dalle_pytorch.py:360-407).

    ``image`` may be token ids (b, n_img) int, raw images (b, H, W, C) float
    (tokenized through the frozen VAE encoder, no gradient — reference
    :375-378 under @torch.no_grad), or None (text-only prefix).
    Returns logits (b, seq, total_tokens) or the scalar CE loss.
    """
    image_ids = None
    if image is not None:
        if image.ndim == 4:
            if vae_params is None:
                raise ValueError("raw images need vae_params to tokenize")
            image_ids = lax.stop_gradient(
                vae_mod.get_codebook_indices(vae_params, image))
        else:
            image_ids = image

    tokens = embed_prompt(params, cfg, text, image_ids)
    seq_len = tokens.shape[1]

    if mask is not None and image_ids is not None:
        pad = jnp.ones((mask.shape[0], image_ids.shape[1]), bool)
        mask = jnp.concatenate([mask, pad], axis=1)

    h, aux = T.transformer_apply(params["transformer"], tokens,
                                 cfg=cfg.transformer, mask=mask, rng=rng,
                                 train=train, with_aux=True)

    if not return_loss:
        with jax.named_scope("head"):
            logits = to_logits(params, h, cfg)
            forbidden = logits_mask(cfg)[:seq_len]
            return jnp.where(forbidden[None], core.neg_inf(logits.dtype),
                             logits)

    if image_ids is None:
        raise ValueError("when training, image must be supplied")
    loss = ce_from_hidden(params, h, text, image_ids, cfg=cfg)
    if cfg.moe_experts:
        loss = loss + cfg.moe_aux_coef * aux
    return loss


@jax.named_scope("loss")
def ce_from_hidden(params: dict, h: Array, text: Array, image_ids: Array, *,
                   cfg: DALLEConfig) -> Array:
    """The training-loss tail shared by every execution path (single-device
    ``dalle_apply`` and the sequence-parallel loss in parallel.sequence):
    labels = [text, image+offset] shifted left with EOS appended, masked
    logits, mean CE (reference dalle_pytorch.py:391-406). Honors
    ``cfg.loss_chunk`` (streamed head)."""
    labels = jnp.concatenate(
        [text, image_ids + cfg.num_text_tokens,
         jnp.full((text.shape[0], 1), cfg.eos_token_id, text.dtype)], axis=1)
    targets = labels[:, 1:]                      # predict token i+1 at row i

    if cfg.loss_chunk > 0:
        return _chunked_ce(params, h, targets, cfg)
    logits = to_logits(params, h)
    forbidden = logits_mask(cfg)[:h.shape[1]]
    logits = jnp.where(forbidden[None], core.neg_inf(logits.dtype), logits)
    return jnp.mean(_nll(logits, targets))


def _nll(logits: Array, targets: Array) -> Array:
    """-log_softmax(logits)[targets] as logsumexp - gathered logit: same
    math, but the full-vocab f32 log-probability tensor (the largest buffer
    in the dense CE head — (b, 1280, 12k) f32 at bench shape) never
    materializes; only the (b, n) reductions do."""
    lse = jax.scipy.special.logsumexp(logits.astype(jnp.float32), axis=-1)
    tgt = jnp.take_along_axis(logits, targets[..., None],
                              axis=-1)[..., 0].astype(jnp.float32)
    return lse - tgt


def _chunked_ce(params: dict, h: Array, targets: Array,
                cfg: DALLEConfig) -> Array:
    """Streamed head + cross-entropy: identical math to the dense path, but
    the (chunk, total_tokens) logits exist only inside a rematerialized scan
    body, so the full (b, seq, total_tokens) tensor is never resident.

    The forbidden-position mask participates BEFORE the log_softmax (it
    shapes the partition function, reference dalle_pytorch.py:391-396), so
    it is applied per chunk, not folded into the gather."""
    b, n, d = h.shape
    chunk = min(cfg.loss_chunk, n)
    pad = (-n) % chunk
    valid = jnp.ones((n,), jnp.float32)
    forbidden = logits_mask(cfg)[:n]
    if pad:
        h = jnp.pad(h, ((0, 0), (0, pad), (0, 0)))
        targets = jnp.pad(targets, ((0, 0), (0, pad)))
        valid = jnp.pad(valid, (0, pad))
        forbidden = jnp.pad(forbidden, ((0, pad), (0, 0)))
    steps = (n + pad) // chunk

    h_c = jnp.moveaxis(h.reshape(b, steps, chunk, d), 1, 0)
    t_c = jnp.moveaxis(targets.reshape(b, steps, chunk), 1, 0)
    f_c = forbidden.reshape(steps, chunk, -1)
    v_c = valid.reshape(steps, chunk)

    def body(acc, xs):
        hc, tc, fc, vc = xs
        logits = to_logits(params, hc)
        logits = jnp.where(fc[None], core.neg_inf(logits.dtype), logits)
        return acc + jnp.sum(_nll(logits, tc) * vc[None]), None

    total, _ = lax.scan(jax.checkpoint(body), jnp.float32(0.0),
                        (h_c, t_c, f_c, v_c))
    return total / (b * n)


# ---------------------------------------------------------------------------
# generation — jit lax.scan sampler with KV cache
# ---------------------------------------------------------------------------

# key bits fixed by one counting pass of ``kth_largest``: 2 is three
# compares over one read of the row and half the passes of 1; on a v5e,
# where the keys stay in VMEM, 0.22 ms for 32 x 128256 float32 against
# 0.27 at 1 and 0.43 at 4 (PERF.md section 6, PR 28)
_SELECT_BITS = 2


@jax.named_scope("sample")
def kth_largest(x: Array, k) -> Array:
    """The ``k``-th largest entry of each row of a float ``x`` (..., n),
    kept as (..., 1), for a ``k`` in 1..n that may be traced and differ
    per row — by exact selection, not by a sort.

    The row is mapped to unsigned keys of the float's own width whose
    order is the floats' (the bits, inverted where the sign is set, the
    sign bit set elsewhere; ``-inf`` lowest, ``-0.0`` just under
    ``0.0``), and the answer's key is fixed from the top, ``_SELECT_BITS``
    a pass: the prefix found so far is extended by the largest digit
    whose candidate still has ``k`` keys at or above it. 8 passes over
    a 16-bit row and 16 over float32, the count taken from the dtype;
    integer compares and counts only. The key found is an entry's own,
    so the value returned compares equal to ``sort(x)[..., n - k]``
    with ties, fills and infinities (a row with NaNs is ordered by
    their bits, not as ``sort`` orders them)."""
    if not jnp.issubdtype(x.dtype, jnp.floating):
        raise TypeError(f"kth_largest orders float rows, got {x.dtype}")
    nbits = x.dtype.itemsize * 8
    uint = jnp.dtype(f"uint{nbits}")
    sign = uint.type(1 << (nbits - 1))
    bits = lax.bitcast_convert_type(x, uint)
    keys = jnp.where(bits >= sign, ~bits, bits | sign)
    k = jnp.broadcast_to(jnp.asarray(k, jnp.int32), x.shape[:-1])[..., None]
    found = jnp.zeros(x.shape[:-1] + (1,), uint)
    for shift in range(nbits - _SELECT_BITS, -1, -_SELECT_BITS):
        digits = np.arange(1, 1 << _SELECT_BITS).astype(uint) << shift
        cand = found | digits
        at_or_above = jnp.sum(keys[..., None, :] >= cand[..., None],
                              axis=-1, dtype=jnp.int32)
        found = jnp.max(jnp.where(at_or_above >= k, cand, found),
                        axis=-1, keepdims=True)
    return lax.bitcast_convert_type(
        jnp.where(found >= sign, found ^ sign, ~found), x.dtype)


@jax.named_scope("sample")
def top_k_filter(logits: Array, thres: float) -> Array:
    """Keep the top (1-thres)·vocab logits, -inf the rest (reference
    top_k helper, dalle_pytorch.py:41-47). The threshold is
    ``kth_largest``'s, the one ``sample_per_slot`` reads."""
    k = max(int((1 - thres) * logits.shape[-1]), 1)
    kth = kth_largest(logits, k)
    return jnp.where(logits < kth, core.neg_inf(logits.dtype), logits)


def _nucleus_threshold(logits: Array, p) -> Array:
    """The smallest logit of each row's nucleus, (..., 1): the descending
    row's softmax and running mass, a token kept while the mass BEFORE
    it is still < ``p`` (a scalar, or (..., 1) for a ``p`` per row) — so
    the argmax always survives, and masked (-inf) tokens carry zero
    mass and sit at cum == 1, never kept for p <= 1. The one place a
    whole row is sorted."""
    sorted_logits = jnp.flip(jnp.sort(logits, axis=-1), axis=-1)
    probs = jax.nn.softmax(sorted_logits.astype(jnp.float32), axis=-1)
    cum = jnp.cumsum(probs, axis=-1)
    keep_sorted = (cum - probs) < p
    return jnp.min(jnp.where(keep_sorted, sorted_logits,
                             jnp.inf).astype(logits.dtype),
                   axis=-1, keepdims=True)


@jax.named_scope("sample")
def top_p_filter(logits: Array, p: float) -> Array:
    """Nucleus filter (beyond reference — the reference samples top-k
    only, dalle_pytorch.py:41-47): keep the smallest prefix of
    descending-probability tokens whose cumulative mass reaches ``p``,
    -inf the rest. Static-shaped (sort + cumsum), so it jits into the
    same one-program sampler as the top-k path. Callers must pass
    TEMPERATURE-SCALED logits: the nucleus is defined on the actual
    sampling distribution."""
    if not 0.0 < p <= 1.0:
        raise ValueError(f"top_p must be in (0, 1], got {p}")
    thresh = _nucleus_threshold(logits, p)
    return jnp.where(logits < thresh, core.neg_inf(logits.dtype), logits)


@jax.named_scope("sample")
def sample_per_slot(logits: Array, pred_pos: Array, keys: Array,
                    temp: Array, topk_k: Array, top_p: Array,
                    cfg: DALLEConfig, *,
                    partner: Optional[Array] = None,
                    cfg_scale: Optional[Array] = None,
                    uncond: Optional[Array] = None,
                    live: Optional[Array] = None) -> Array:
    """Per-slot sampling: the traced-parameter form of ``generate_images``'s
    ``sample`` — forbidden-position mask, temperature, top-k OR nucleus
    filter, categorical — with every knob a (slots,) array instead of a
    python constant, so the serve engine's one compiled program covers any
    per-request mix (serve/engine.py holds the equivalence contract).

    Value-identical to the one-shot path per slot: the top-k threshold is
    the k-th largest logit at a per-slot k, selected exactly by
    ``kth_largest`` (``top_k_filter`` reads the same helper) — counting
    passes over the row, never an order of it; the nucleus threshold is
    ``top_p_filter``'s exact math (``_nucleus_threshold``) with p
    broadcast per slot, and its sort runs under a ``lax.cond`` only on
    a step where some ``live`` slot (every slot when ``live`` is None)
    has ``top_p > 0``: a pool of top-k requests never sorts, and the
    program is still one trace. ``top_p > 0`` selects nucleus per slot,
    exactly as the python-level branch does in ``generate_images`` (a
    dead slot's stale ``top_p`` selects an unfiltered row whose token
    nobody reads). Per-slot draws go through
    ``fold_in(key, pred_pos)`` — the one-shot sampler's key discipline —
    and ``jax.random.categorical`` over one slot's (vocab,) row equals
    the batch-1 call with the same key. Returns sampled ids with the
    text-vocab offset removed for image positions, as ``generate_images``
    stores them.

    ``partner``/``cfg_scale``/``uncond`` (all (slots,); pass together or
    not at all) fold per-request classifier-free guidance into the SAME
    program: a guided request occupies a cond/uncond slot pair (each the
    other's ``partner``; self elsewhere), and a cond slot with
    ``cfg_scale > 0`` samples image positions from
    ``l_uncond + cfg_scale * (l_cond - l_uncond)`` — the identical
    formula, f32 mix, and cast of ``generate_images``' guided ``sample``
    — while its uncond partner takes the cond slot's drawn token (the
    one-shot path's ``tile``), so the pair's caches stay in step. Text
    positions sample from the cond stream alone, exactly as one-shot."""
    lg = jnp.where(logits_mask_rows(cfg, pred_pos - 1),
                   core.neg_inf(logits.dtype), logits)
    if partner is not None:
        # guided mix BEFORE temperature, on the masked logits — the
        # one-shot ``sample``'s order. f32: the forbidden fill is
        # -finfo.max and the extrapolation must not overflow it.
        l_self = lg.astype(jnp.float32)
        l_pair = jnp.take(lg, partner, axis=0).astype(jnp.float32)
        # on a cond slot the partner IS the uncond stream: the mix
        # below is literally l_u + scale * (l_c - l_u)
        mix = (l_pair + cfg_scale[:, None] * (l_self - l_pair)) \
            .astype(lg.dtype)
        guided_img = ((cfg_scale > 0) & ~uncond
                      & (pred_pos >= cfg.text_seq_len))
        lg = jnp.where(guided_img[:, None], mix, lg)
    lg = lg / temp[:, None]

    # both filters cut a row below a threshold, so a slot selects its
    # threshold, not its filtered row
    wants = top_p > 0
    nucleus = lax.cond(
        jnp.any(wants if live is None else live & wants),
        lambda: _nucleus_threshold(lg, top_p[:, None]),
        lambda: jnp.full((lg.shape[0], 1), -jnp.inf, lg.dtype))
    thresh = jnp.where(wants[:, None], nucleus, kth_largest(lg, topk_k))
    lg = jnp.where(lg < thresh, core.neg_inf(lg.dtype), lg)
    folded = jax.vmap(jax.random.fold_in)(keys, pred_pos)
    raw = jax.vmap(jax.random.categorical)(folded, lg)
    if partner is not None:
        # the uncond slot takes its cond partner's drawn token — the
        # one-shot guided path's ``tile(raw, 2)``: both streams of a
        # pair consume the same token so their KV caches agree
        raw = jnp.where((cfg_scale > 0) & uncond,
                        jnp.take(raw, partner), raw)
    is_image = pred_pos >= cfg.text_seq_len
    return jnp.where(is_image, raw - cfg.num_text_tokens, raw)


def generate_images(params: dict, vae_params: dict, text: Array, *,
                    cfg: DALLEConfig, rng: Array,
                    mask: Optional[Array] = None,
                    filter_thres: float = 0.5,
                    top_p: float = 0.0,
                    temperature: float = 1.0,
                    guidance: float = 0.0,
                    clip_params: Optional[dict] = None,
                    clip_cfg=None,
                    return_img_seq: bool = False,
                    quantize_cache: bool = False):
    """Sample image tokens autoregressively, decode through the VAE.

    Matches the reference sampling distribution (reference
    dalle_pytorch.py:317-358): per step the masked logits are top-k filtered
    (keep top half by default) and sampled at ``temperature``; prompts
    shorter than text_seq_len are completed through the text span first
    (genDALLE's unpadded-prompt mode). With ``clip_params`` the generated
    images are scored by CLIP (reference :354-356).

    ``guidance`` > 0 enables classifier-free guidance (beyond reference):
    a second, unconditional stream — the all-PAD null caption — rides in
    the batch dimension of the SAME one-program scan, and each image
    token samples from ``l_uncond + guidance * (l_cond - l_uncond)``
    (guidance 1.0 reduces to conditional sampling). Both streams consume
    the same sampled image tokens so their KV caches agree; text
    positions sample from the conditional stream alone while the null
    stream keeps PAD. Train with ``--caption_drop`` so the model has
    seen null captions.

    ``quantize_cache`` stores the KV cache int8 with per-row scales
    (ops.decode.init_cache) — halves the cache's share of per-token HBM
    reads (bench.decode_roofline_ms_per_token quantifies it; the term
    dominates at batch > 1). Composes with ``quantize_for_decode``
    (int8 weights) for the full int8 decode path, and with the serving
    engine's PAGED KV layout (serve/kv_pool.py): the int8 page pool
    carries the same per-row scales per page, quantizes through the
    same ``_quantize_rows``, and obeys the identical error contract —
    int8 halves the bytes per page exactly as it halves them per dense
    row, so the two HBM levers multiply. Accuracy: the int8
    rows plus the scale-cast-to-score-dtype under bf16 compound to a
    ~1% relative attention-output error bound per layer (see
    ops.decode.init_cache); tests/test_quant.py's 2% end-to-end parity
    tolerance is that contract. There is no opt-out short of
    ``quantize_cache=False``.
    """
    if clip_params is not None and \
            clip_cfg.num_text_tokens < cfg.num_text_tokens:
        # an undersized CLIP vocab would make the rerank's embedding
        # gather go out of range on sampled text ids — which jnp.take
        # (default mode='fill') turns into NaN latents and NaN scores
        # with no error. Fail before the expensive sampling scan instead
        # (config-only check, so eager callers fail fast too).
        raise ValueError(
            f"CLIP num_text_tokens ({clip_cfg.num_text_tokens}) < "
            f"DALLE num_text_tokens ({cfg.num_text_tokens}): the "
            "rerank would gather out-of-range text ids (NaN scores); "
            "train CLIP with a vocab covering the DALLE's")
    b, t0 = text.shape
    total_len = cfg.seq_len
    tcfg = cfg.transformer
    if tcfg.block is not None:
        raise T.BlockOptionError(tcfg.block.name, "generate_images",
                                 "the one-shot sampler decodes over the "
                                 "dense cache")

    guided = guidance > 0
    if guided:
        # unconditional stream = the all-PAD null caption, batched below
        # the conditional rows so one scan serves both
        text = jnp.concatenate([text, jnp.zeros_like(text)], axis=0)
        if mask is not None:
            # the null stream gets an all-True mask: --caption_drop
            # training attends every PAD position of a dropped caption
            # (loss_fn's all-True mask), and the uncond baseline must
            # match that distribution
            mask = jnp.concatenate([mask, jnp.ones_like(mask)], axis=0)
    rows = text.shape[0]

    tokens = embed_prompt(params, cfg, text)
    h, cache = decode_ops.prefill(params["transformer"], tokens, cfg=tcfg,
                                  total_len=total_len, prompt_mask=mask,
                                  quantize_cache=quantize_cache)
    key_mask = decode_ops._full_key_mask(mask, rows, t0, total_len)
    forbidden = logits_mask(cfg)
    uncond_rows = jnp.arange(rows) >= b

    @jax.named_scope("sample")
    def sample(logits_row, pred_pos, key):
        """Sample the token for position pred_pos from last-row logits."""
        lg = jnp.where(forbidden[pred_pos - 1][None], core.neg_inf(
            logits_row.dtype), logits_row)
        is_image = pred_pos >= cfg.text_seq_len
        if guided:
            # mix in f32: the forbidden fill is -finfo.max and the
            # extrapolation below must not overflow it
            l_c = lg[:b].astype(jnp.float32)
            l_u = lg[b:].astype(jnp.float32)
            mix = l_u + guidance * (l_c - l_u)
            lg = jnp.where(is_image, mix, l_c).astype(lg.dtype)
        # temperature first: the nucleus must hold p mass of the ACTUAL
        # sampling distribution (top-k is rank-preserving, so the reorder
        # is behavior-neutral for the reference path). Static python
        # branch: top_p > 0 selects nucleus, else reference top-k.
        lg = lg / temperature
        lg = (top_p_filter(lg, top_p) if top_p > 0
              else top_k_filter(lg, filter_thres))
        raw = jax.random.categorical(key, lg, axis=-1)
        if guided:
            raw = jnp.tile(raw, 2)       # both streams take the same token
        return jnp.where(is_image, raw - cfg.num_text_tokens, raw)

    # token for position t0 from the prefill's last row
    first_tok = sample(to_logits(params, h[:, -1]), t0,
                       jax.random.fold_in(rng, t0))

    def step(carry, pos):
        cur_tok, cache = carry
        is_text = pos < cfg.text_seq_len
        if guided:
            # the null stream's text stays PAD — feeding it the sampled
            # caption would make it conditional
            cur_tok = jnp.where(is_text & uncond_rows, 0, cur_tok)
        x = decode_token_embed(params, cfg, cur_tok, pos)

        h_tok, cache = decode_ops.decode_step(params["transformer"], x, pos,
                                              cache, cfg=tcfg,
                                              key_mask=key_mask)
        nxt = sample(to_logits(params, h_tok), pos + 1,
                     jax.random.fold_in(rng, pos + 1))
        return (nxt, cache), cur_tok

    positions = jnp.arange(t0, total_len)
    (_, _), toks = lax.scan(step, (first_tok, cache), positions)
    toks = jnp.moveaxis(toks, 0, 1)                  # (rows, total_len - t0)

    full = jnp.concatenate([text, toks], axis=1)[:b]   # cond stream only
    img_seq = full[:, -cfg.image_seq_len:]
    images = vae_mod.decode(vae_params, img_seq,
                            codebook=params["image_emb"]["w"])

    if return_img_seq:
        return images, img_seq
    if clip_params is not None:
        from dalle_pytorch_tpu.models import clip as clip_mod
        text_seq = full[:, :cfg.text_seq_len]
        scores = clip_mod.clip_apply(clip_params, text_seq, images,
                                     cfg=clip_cfg)
        return images, scores
    return images


# ---------------------------------------------------------------------------
# OO wrapper for reference-API parity
# ---------------------------------------------------------------------------

class DALLE:
    """Reference-shaped facade (reference dalle_pytorch.py:241-407) over the
    functional core. Holds its own params plus the VAE it tokenizes/decodes
    through."""

    def __init__(self, *, dim: int, vae: vae_mod.DiscreteVAE, depth: int,
                 key: Optional[Array] = None, params: Optional[dict] = None,
                 dtype=jnp.float32, **cfg_kwargs):
        if not isinstance(vae, vae_mod.DiscreteVAE):
            raise TypeError("vae must be a DiscreteVAE")
        self.vae = vae
        self.config = DALLEConfig(dim=dim, depth=depth, vae=vae.config,
                                  **cfg_kwargs)
        if params is None:
            if key is None:
                key = jax.random.PRNGKey(0)
            params = dalle_init(key, self.config, vae.params, dtype)
        self.params = params

    def __call__(self, text: Array, image=None, mask: Optional[Array] = None,
                 return_loss: bool = False, rng: Optional[Array] = None,
                 train: bool = False):
        return dalle_apply(self.params, text, image, cfg=self.config,
                           mask=mask, vae_params=self.vae.params, rng=rng,
                           train=train, return_loss=return_loss)

    forward = __call__

    def generate_images(self, text: Array, *, rng: Optional[Array] = None,
                        clip=None, mask: Optional[Array] = None,
                        filter_thres: float = 0.5, top_p: float = 0.0,
                        guidance: float = 0.0, temperature: float = 1.0):
        if rng is None:
            rng = jax.random.PRNGKey(0)
        kwargs = {}
        if clip is not None:
            kwargs = {"clip_params": clip.params, "clip_cfg": clip.config}
        return generate_images(self.params, self.vae.params, text,
                               cfg=self.config, rng=rng, mask=mask,
                               filter_thres=filter_thres, top_p=top_p,
                               guidance=guidance,
                               temperature=temperature, **kwargs)

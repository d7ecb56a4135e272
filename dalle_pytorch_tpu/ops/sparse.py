"""Block-sparse attention layout + XLA reference implementation.

Replicates the semantics the reference gets from DeepSpeed's
``SparseSelfAttention(VariableSparsityConfig(num_heads, block=16,
attention='unidirectional'))`` (reference dalle_pytorch/transformer.py:91-135):

  * the sequence is tiled into blocks of ``block`` tokens (16 in the
    reference);
  * queries attend within their **local window** of ``num_local_blocks``
    consecutive blocks (VariableSparsityConfig default: 4 blocks — windows are
    the non-overlapping groups [0..3], [4..7], ...);
  * every query additionally attends to the **global blocks**
    (default: block 0);
  * causal masking on top for unidirectional attention;
  * inputs are padded to a block multiple, pad **keys** are masked
    (key_padding_mask — unlike the dense path, pad queries are NOT masked,
    reference transformer.py:120-122), and the output is sliced back
    (reference transformer.py:109-135).

``sparse_attention_ref`` is the numerics oracle: dense softmax restricted to
the layout. The Pallas kernel (ops.block_sparse) must agree with it; the
transformer picks between them with ``sparse_impl``.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from dalle_pytorch_tpu.ops import core

Array = jax.Array


@functools.lru_cache(maxsize=32)
def variable_sparsity_layout(num_blocks: int, *, num_local_blocks: int = 4,
                             global_blocks: Tuple[int, ...] = (0,),
                             causal: bool = True) -> np.ndarray:
    """(num_blocks, num_blocks) bool — True where block (q, k) is attended."""
    ib = np.arange(num_blocks)[:, None]
    jb = np.arange(num_blocks)[None, :]
    same_window = (ib // num_local_blocks) == (jb // num_local_blocks)
    layout = same_window
    for g in global_blocks:
        layout = layout | (jb == g)
    if causal:
        layout = layout & (jb <= ib)
    return layout


def token_layout_mask(seq_len: int, block: int = 16, *,
                      num_local_blocks: int = 4,
                      global_blocks: Tuple[int, ...] = (0,),
                      causal: bool = True) -> np.ndarray:
    """Expand the block layout to a (seq_len, seq_len) token mask (True=keep).

    The causal constraint here is block-level only; the token-level strict
    triangle is applied separately (matching DeepSpeed, which combines a block
    layout with an additive token-level causal mask,
    reference transformer.py:124-130).
    """
    assert seq_len % block == 0
    nb = seq_len // block
    layout = variable_sparsity_layout(
        nb, num_local_blocks=num_local_blocks, global_blocks=global_blocks,
        causal=causal)
    return np.repeat(np.repeat(layout, block, axis=0), block, axis=1)


def visible_pages(seq_len: int, page_size: int, block: int = 16, *,
                  num_local_blocks: int = 4,
                  global_blocks: Tuple[int, ...] = (0,),
                  causal: bool = True) -> Tuple[np.ndarray, np.ndarray]:
    """Per-position visible KV-page sets under the VariableSparsity layout.

    The layout is STATIC (config only), so "which pages can position p
    see" is a precomputable fact: for pages of ``page_size`` rows, page g
    is visible at position p iff ANY token in ``[g*page_size,
    (g+1)*page_size)`` is allowed by row p of ``token_layout_mask`` —
    the any-token-in-page reduction. Because the layout is a local
    window plus the global blocks (the text anchor), the visible set is
    tiny and near-constant in ``seq_len``, which is what makes
    sparsity-aware decode reads worth it (ops.decode /
    ops.paged_attention consume these tables; docs/SERVING.md "Sparse
    decode reads").

    Returns ``(vis, cnt)``: ``vis`` is ``(seq_len, W)`` int32 with row p
    listing p's visible page ids in ASCENDING order (``W`` = the max
    count over positions — the static width a fixed-shape decode
    program needs), padded with 0 past ``cnt[p]``; ``cnt`` is
    ``(seq_len,)`` int32. Padding entries are NOT visibility grants —
    consumers must mask columns >= cnt[p] (page 0 genuinely visible is
    always listed inside the counted prefix).
    """
    if page_size < 1:
        raise ValueError(f"page_size must be >= 1, got {page_size}")
    padded = ((seq_len + block - 1) // block) * block
    layout = token_layout_mask(padded, block,
                               num_local_blocks=num_local_blocks,
                               global_blocks=global_blocks,
                               causal=causal)[:seq_len, :seq_len]
    num_pages = -(-seq_len // page_size)
    pad_cols = num_pages * page_size - seq_len
    if pad_cols:
        layout = np.pad(layout, ((0, 0), (0, pad_cols)))
    page_vis = layout.reshape(seq_len, num_pages, page_size).any(-1)
    cnt = page_vis.sum(-1).astype(np.int32)
    width = max(int(cnt.max()), 1)
    # stable argsort of ~visible floats the visible page ids to the
    # front of each row IN ascending-page order (stability keeps it)
    order = np.argsort(~page_vis, axis=1, kind="stable")[:, :width]
    vis = order.astype(np.int32)
    vis[np.arange(width)[None, :] >= cnt[:, None]] = 0
    return vis, cnt


@functools.lru_cache(maxsize=32)
def visible_pages_causal(seq_len: int, page_size: int, block: int = 16, *,
                         num_local_blocks: int = 4,
                         global_blocks: Tuple[int, ...] = (0,),
                         causal: bool = True
                         ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Cached ``visible_pages`` plus the DECODE trip count — the one
    shared source for the sparse-reads step math (ops.decode), the
    engine's /stats read-bytes model (serve.engine), and bench, so the
    three can never drift on what "visible" means. ``cnt_causal[p]``
    counts the visible pages starting strictly before p (a page at or
    past p holds no readable rows yet); the visible list is ascending,
    so the causal subset is a PREFIX of it. The returned arrays are
    frozen (write=False): the cache shares them across callers, and an
    in-place edit would silently corrupt every later consumer's
    visibility."""
    vis, cnt = visible_pages(seq_len, page_size, block,
                             num_local_blocks=num_local_blocks,
                             global_blocks=global_blocks, causal=causal)
    width = vis.shape[1]
    live = np.arange(width)[None, :] < cnt[:, None]
    before = vis * page_size < np.arange(seq_len)[:, None]
    cnt_causal = (live & before).sum(1).astype(np.int32)
    for a in (vis, cnt, cnt_causal):
        a.setflags(write=False)
    return vis, cnt, cnt_causal


@jax.named_scope("attn.read")
def sparse_attention_ref(q: Array, k: Array, v: Array, *, scale: float,
                         causal: bool, block: int = 16,
                         mask: Optional[Array] = None,
                         num_local_blocks: int = 4,
                         global_blocks: Tuple[int, ...] = (0,)) -> Array:
    """Dense-math oracle for block-sparse attention.

    q, k, v: (b, h, n, d). ``mask``: (b, n) key-padding mask (True = keep).
    Assumes n is a block multiple (the transformer pads beforehand, as the
    reference does at transformer.py:112-115).
    """
    b, h, n, d = q.shape
    dots = jnp.einsum("bhid,bhjd->bhij", q, k) * scale

    # two-fill semantics shared with the Pallas kernels (see
    # ops.flash_attention docstring): structural masks (layout + causal)
    # are -inf, pad keys are the finite fill.
    layout = jnp.asarray(token_layout_mask(
        n, block, num_local_blocks=num_local_blocks,
        global_blocks=global_blocks, causal=causal))
    structural = layout[None, None, :, :]
    if causal:
        tri = jnp.tril(jnp.ones((n, n), bool))
        structural = structural & tri[None, None, :, :]

    if mask is not None:
        dots = jnp.where(mask[:, None, None, :], dots,
                         core.neg_inf(dots.dtype))  # key padding only
    dots = jnp.where(structural, dots, -jnp.inf)
    attn = jax.nn.softmax(dots, axis=-1)
    return jnp.einsum("bhij,bhjd->bhid", attn, v)


@jax.named_scope("attn.read")
def sparse_attention_windowed(q: Array, k: Array, v: Array, *, scale: float,
                              causal: bool, block: int = 16,
                              mask: Optional[Array] = None,
                              num_local_blocks: int = 4,
                              global_blocks: Tuple[int, ...] = (0,)) -> Array:
    """Exact VariableSparsity attention via its algebraic structure.

    The layout is (same non-overlapping window) | (global block columns)
    [& causal], so each query row's allowed columns are its own W-token
    window plus the G global tokens. Computing a block-diagonal (W, W)
    window piece and a narrow (n, G) global strip and softmaxing ONCE over
    the concatenated (W + G) columns reproduces ``sparse_attention_ref``
    bit-for-bit semantics (same two-fill masking) while doing n*(W+G)
    work instead of n^2 — at the reference layout (block 16, window 4
    blocks, one global block) and seq 1280 that is a 16x FLOP cut, in the
    autodiff BACKWARD too, with nothing but dense MXU-friendly einsums (no
    custom kernel, no (n, n) buffer). This is the fast training path; the
    Pallas kernel (ops.block_sparse) and the dense oracle remain as the
    cross-checked alternatives.
    """
    b, h, n, d = q.shape
    W = num_local_blocks * block
    gcols = np.concatenate([np.arange(g * block, (g + 1) * block)
                            for g in global_blocks])
    if (gcols >= n).any():
        raise ValueError(f"global blocks {global_blocks} out of range for "
                         f"seq {n} (block {block})")
    G = len(gcols)
    pad = (-n) % W
    if pad:
        q, k, v = (jnp.pad(x, ((0, 0), (0, 0), (0, pad), (0, 0)))
                   for x in (q, k, v))
    n_p = n + pad
    nw = n_p // W
    fill = core.neg_inf(jnp.float32)

    qw = q.reshape(b, h, nw, W, d)
    kw = k.reshape(b, h, nw, W, d)
    vw = v.reshape(b, h, nw, W, d)

    # window piece: block-diagonal (W, W) scores
    s_w = jnp.einsum("bhwid,bhwjd->bhwij", qw, kw,
                     preferred_element_type=jnp.float32) * scale
    if mask is not None:
        mw = jnp.pad(mask, ((0, 0), (0, pad)))  # pad keys masked (keys-only
        mw = mw.reshape(b, 1, nw, 1, W)         # contract, ref :120-122)
        s_w = jnp.where(mw, s_w, fill)
    rows_w = np.arange(W)[:, None]
    cols_w = np.arange(W)[None, :]
    colidx = (np.arange(nw)[:, None, None] * W
              + cols_w[None])                   # (nw, 1, W) absolute col
    allow_w = np.broadcast_to(colidx < n, (nw, W, W))
    if causal:
        allow_w = allow_w & (cols_w <= rows_w)[None]
    s_w = jnp.where(jnp.asarray(allow_w)[None, None], s_w, -jnp.inf)

    # global strip: every row vs the G global columns
    kg = k[:, :, gcols]
    vg = v[:, :, gcols]
    s_g = jnp.einsum("bhid,bhgd->bhig", q, kg,
                     preferred_element_type=jnp.float32) * scale
    if mask is not None:
        s_g = jnp.where(mask[:, gcols][:, None, None, :], s_g, fill)
    rows = np.arange(n_p)[:, None]
    # columns already counted by the row's own window must not double-count
    allow_g = (gcols[None, :] // W) != (rows // W)
    if causal:
        allow_g = allow_g & (gcols[None, :] <= rows)
    s_g = jnp.where(jnp.asarray(allow_g)[None, None], s_g, -jnp.inf)

    # one safe softmax over the union of both pieces' columns
    s_cat = jnp.concatenate([s_w, s_g.reshape(b, h, nw, W, G)], axis=-1)
    m = s_cat.max(axis=-1, keepdims=True)
    p = jnp.exp(s_cat - jnp.where(jnp.isfinite(m), m, 0.0))
    p = jnp.where(jnp.isfinite(s_cat), p, 0.0)
    l = p.sum(axis=-1, keepdims=True)
    v_cat = jnp.concatenate(
        [vw, jnp.broadcast_to(vg[:, :, None], (b, h, nw, G, d))], axis=3)
    out = jnp.einsum("bhwij,bhwjd->bhwid", p.astype(v_cat.dtype), v_cat,
                     preferred_element_type=jnp.float32)
    out = out / jnp.where(l == 0.0, 1.0, l)
    return out.reshape(b, h, n_p, d)[:, :, :n].astype(q.dtype)

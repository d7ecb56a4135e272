"""The sampler's thresholds without an order of the vocabulary.

``models.dalle.kth_largest`` against ``jnp.sort`` (every k, ties, fills,
infinities, signed zeros, both logit widths), and ``sample_per_slot``
token for token against the sort-based formula it replaced, kept here as
the oracle — over batches with no nucleus slot, one, and a mix with a
guided pair, so both branches of the sampler's conditional are compared.
"""

import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dalle_pytorch_tpu.models import dalle as D
from dalle_pytorch_tpu.ops import core

VOCAB = 515          # not a multiple of any tile
DTYPES = {"float32": jnp.float32, "bfloat16": jnp.bfloat16}


def _rows(dtype) -> jnp.ndarray:
    """One row a hazard: plain, ties everywhere (rounded), all forbidden
    fill but a few, ``-inf`` entries (the fill over a temperature below
    1), ``-0.0`` beside ``0.0`` among both signs, one value throughout."""
    rng = np.random.default_rng(7)
    x = rng.normal(size=(6, VOCAB)).astype(np.float32) * 4
    x[1] = np.round(x[1])
    x[2, 5:] = float(core.neg_inf(dtype))
    x[3, ::3] = -np.inf
    x[4, :40] = 0.0
    x[4, 40:80] = -0.0
    x[5] = 1.5
    return jnp.asarray(x).astype(dtype)


def _sorted_kth(x, k):
    desc = jnp.flip(jnp.sort(x, axis=-1), axis=-1)
    return jnp.take_along_axis(desc, (k - 1)[:, None], axis=-1)


KS = {"one": [1] * 6, "two": [2] * 6, "half": [VOCAB // 2] * 6,
      "all_but_one": [VOCAB - 1] * 6, "all": [VOCAB] * 6,
      "per_slot": [1, 40, 3, VOCAB, 60, 257],
      # the k-th place inside a run of equal values, and at its two ends
      "in_ties": [7, 41, 5, 344, 80, 300]}


@pytest.mark.parametrize("ks", list(KS))
@pytest.mark.parametrize("dtype", list(DTYPES))
def test_kth_largest_equals_sorted_row(dtype, ks):
    x = _rows(DTYPES[dtype])
    k = jnp.asarray(KS[ks], jnp.int32)
    got = jax.jit(D.kth_largest)(x, k)
    assert got.dtype == x.dtype and got.shape == (6, 1)
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(_sorted_kth(x, k), np.float32))


@pytest.mark.parametrize("dtype", list(DTYPES))
def test_kth_largest_every_k_of_a_tied_row(dtype):
    """Every k in 1..n on a short row of few distinct values."""
    row = jnp.asarray([2.0, -1.0, 2.0, 0.0, -0.0, -jnp.inf, 7.5, -1.0,
                       float(core.neg_inf(DTYPES[dtype])), 2.0],
                      DTYPES[dtype])
    n = row.shape[0]
    x = jnp.tile(row, (n, 1))
    k = jnp.arange(1, n + 1, dtype=jnp.int32)
    np.testing.assert_array_equal(
        np.asarray(D.kth_largest(x, k), np.float32),
        np.asarray(_sorted_kth(x, k), np.float32))


def test_kth_largest_takes_a_static_k_and_refuses_integers():
    x = _rows(jnp.float32)
    np.testing.assert_array_equal(
        np.asarray(D.kth_largest(x, 3)),
        np.asarray(_sorted_kth(x, jnp.full((6,), 3, jnp.int32))))
    with pytest.raises(TypeError, match="float"):
        D.kth_largest(jnp.arange(6).reshape(2, 3), 1)


@pytest.mark.parametrize("thres", [0.0, 0.5, 0.9, 0.999])
@pytest.mark.parametrize("dtype", list(DTYPES))
def test_top_k_filter_equals_lax_top_k(dtype, thres):
    """The one-shot filter reads the helper: the same rows kept as the
    ``lax.top_k`` threshold it read before."""
    x = _rows(DTYPES[dtype])
    k = max(int((1 - thres) * VOCAB), 1)
    kth = jax.lax.top_k(x, k)[0][..., -1:]
    want = jnp.where(x < kth, core.neg_inf(x.dtype), x)
    np.testing.assert_array_equal(
        np.asarray(D.top_k_filter(x, thres), np.float32),
        np.asarray(want, np.float32))


# -- sample_per_slot against the formula it replaced ---------------------------

CFG = types.SimpleNamespace(seq_len=24, total_tokens=83, text_seq_len=8,
                            num_text_tokens=50)


def sorted_sample_per_slot(logits, pred_pos, keys, temp, topk_k, top_p, cfg,
                           *, partner=None, cfg_scale=None, uncond=None):
    """``sample_per_slot`` as it stood while it sorted every row every
    step: both thresholds off one descending sort. The oracle."""
    lg = jnp.where(D.logits_mask_rows(cfg, pred_pos - 1),
                   core.neg_inf(logits.dtype), logits)
    if partner is not None:
        l_self = lg.astype(jnp.float32)
        l_pair = jnp.take(lg, partner, axis=0).astype(jnp.float32)
        mix = (l_pair + cfg_scale[:, None] * (l_self - l_pair)) \
            .astype(lg.dtype)
        guided_img = ((cfg_scale > 0) & ~uncond
                      & (pred_pos >= cfg.text_seq_len))
        lg = jnp.where(guided_img[:, None], mix, lg)
    lg = lg / temp[:, None]
    sorted_desc = jnp.flip(jnp.sort(lg, axis=-1), axis=-1)
    kth = jnp.take_along_axis(sorted_desc, (topk_k - 1)[:, None], axis=-1)
    by_k = jnp.where(lg < kth, core.neg_inf(lg.dtype), lg)
    probs = jax.nn.softmax(sorted_desc.astype(jnp.float32), axis=-1)
    cum = jnp.cumsum(probs, axis=-1)
    keep_sorted = (cum - probs) < top_p[:, None]
    thresh = jnp.min(jnp.where(keep_sorted, sorted_desc,
                               jnp.inf).astype(lg.dtype),
                     axis=-1, keepdims=True)
    by_p = jnp.where(lg < thresh, core.neg_inf(lg.dtype), lg)
    lg = jnp.where((top_p > 0)[:, None], by_p, by_k)
    folded = jax.vmap(jax.random.fold_in)(keys, pred_pos)
    raw = jax.vmap(jax.random.categorical)(folded, lg)
    if partner is not None:
        raw = jnp.where((cfg_scale > 0) & uncond,
                        jnp.take(raw, partner), raw)
    is_image = pred_pos >= cfg.text_seq_len
    return jnp.where(is_image, raw - cfg.num_text_tokens, raw)


SLOTS = 6
HALF = CFG.total_tokens // 2
# (temperature, k, top_p) a slot: greedy, the reference's default
# filter_thres 0.5, nucleus
GREEDY, TOP_HALF, NUCLEUS = (1.0, 1, 0.0), (0.7, HALF, 0.0), (1.3, 1, 0.9)
BATCHES = {
    "no_nucleus": [GREEDY, TOP_HALF, GREEDY, TOP_HALF, (1.0, 5, 0.0),
                   (0.5, CFG.total_tokens, 0.0)],
    "one_nucleus": [GREEDY, TOP_HALF, NUCLEUS, GREEDY, TOP_HALF, GREEDY],
    "all_nucleus": [NUCLEUS, (0.8, 1, 0.5), NUCLEUS, (1.0, 1, 1.0),
                    NUCLEUS, (2.0, 1, 0.05)],
}


def _knobs(batch):
    t, k, p = zip(*BATCHES[batch])
    return (jnp.asarray(t, jnp.float32), jnp.asarray(k, jnp.int32),
            jnp.asarray(p, jnp.float32))


def _draws(fn, logits, knobs, **kw):
    """Tokens of ``fn`` over text, boundary and image positions, three
    draws a position."""
    keys = jax.random.split(jax.random.PRNGKey(3), SLOTS)
    step = jax.jit(lambda lg, pos, ks: fn(lg, pos, ks, *knobs, CFG, **kw))
    out = []
    for pos in (2, CFG.text_seq_len - 1, CFG.text_seq_len, 15,
                CFG.seq_len - 1):
        for draw in range(3):
            pred_pos = jnp.full((SLOTS,), pos, jnp.int32) \
                .at[1].set(min(pos + 1, CFG.seq_len - 1))
            out.append(step(logits + draw, pred_pos,
                            jax.vmap(jax.random.fold_in, (0, None))(
                                keys, draw)))
    return np.asarray(jnp.stack(out))


@pytest.mark.parametrize("batch", list(BATCHES))
@pytest.mark.parametrize("dtype", list(DTYPES))
def test_sample_per_slot_tokens_equal_the_sorted_formula(dtype, batch):
    logits = (jax.random.normal(jax.random.PRNGKey(1),
                                (SLOTS, CFG.total_tokens)) * 3) \
        .astype(DTYPES[dtype])
    # ties at the top and at the k-th place of the filtered rows
    logits = logits.at[0, 50:54].set(9.0).at[1, 55:75].set(1.0)
    knobs = _knobs(batch)
    np.testing.assert_array_equal(
        _draws(D.sample_per_slot, logits, knobs),
        _draws(sorted_sample_per_slot, logits, knobs))


@pytest.mark.parametrize("batch", ["no_nucleus", "one_nucleus"])
def test_sample_per_slot_guided_pair_equals_the_sorted_formula(batch):
    """Slots 0/1 a guided cond/uncond pair, slots 4/5 an unguided one."""
    logits = jax.random.normal(jax.random.PRNGKey(2),
                               (SLOTS, CFG.total_tokens)) * 3
    pair = dict(partner=jnp.asarray([1, 0, 2, 3, 5, 4], jnp.int32),
                cfg_scale=jnp.asarray([3.0, 3.0, 0, 0, 0, 0], jnp.float32),
                uncond=jnp.asarray([0, 1, 0, 0, 0, 1], bool))
    knobs = _knobs(batch)
    np.testing.assert_array_equal(
        _draws(D.sample_per_slot, logits, knobs, **pair),
        _draws(sorted_sample_per_slot, logits, knobs, **pair))


def test_a_dead_slot_does_not_ask_for_the_sort():
    """``live`` masks the predicate, not the result: a dead slot's stale
    ``top_p`` leaves every live slot's token as it was, and the program
    holds the sort only inside the conditional's branch."""
    logits = jax.random.normal(jax.random.PRNGKey(4),
                               (SLOTS, CFG.total_tokens)) * 3
    knobs = _knobs("one_nucleus")
    live = jnp.ones((SLOTS,), bool).at[2].set(False)
    got = _draws(D.sample_per_slot, logits, knobs, live=live)
    want = _draws(sorted_sample_per_slot, logits, knobs)
    keep = np.asarray(live)
    np.testing.assert_array_equal(got[:, keep], want[:, keep])

    pos = jnp.full((SLOTS,), 12, jnp.int32)
    keys = jax.random.split(jax.random.PRNGKey(3), SLOTS)
    jaxpr = jax.make_jaxpr(lambda lg, live: D.sample_per_slot(
        lg, pos, keys, *knobs, CFG, live=live))(logits, live)
    conds = _conds_with_a_sort_outside_none(jaxpr.jaxpr)
    assert len(conds) == 1
    assert sorted(_sorts(b.jaxpr) for b in conds[0].params["branches"]) \
        == [0, 1]


def _subjaxprs(eqn):
    for v in eqn.params.values():
        for j in (v if isinstance(v, (tuple, list)) else (v,)):
            if hasattr(j, "eqns"):
                yield j
            elif hasattr(j, "jaxpr") and hasattr(j.jaxpr, "eqns"):
                yield j.jaxpr


def _sorts(jaxpr) -> int:
    return sum((e.primitive.name == "sort")
               + sum(_sorts(j) for j in _subjaxprs(e)) for e in jaxpr.eqns)


def _conds_with_a_sort_outside_none(jaxpr) -> list:
    """The ``cond`` equations of ``jaxpr``, found through nested calls;
    fails if a sort lies anywhere but under one of them."""
    conds = []
    for e in jaxpr.eqns:
        assert e.primitive.name != "sort", "a sort outside the conditional"
        if e.primitive.name == "cond":
            conds.append(e)
        else:
            for j in _subjaxprs(e):
                conds += _conds_with_a_sort_outside_none(j)
    return conds

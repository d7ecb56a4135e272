"""Bytes that the algorithm needs, from shapes alone: what this family's
readers name (``metrics/ssm_step_roofline.py``,
``metrics/gqa_read_roofline.py``). Both are memory-bound at a decode
step's few dozen rows, so only bytes are here."""

from __future__ import annotations


def kv_page_bytes(d, page_size: int, itemsize: int = 2) -> float:
    """One page of ONE layer: ``page_size`` rows of K and of V for every
    key/value head."""
    return 2.0 * d.kv_heads * page_size * d.head_dim * itemsize


def gqa_read_bytes(d, full_pages: float, window_pages: float,
                   page_size: int, itemsize: int = 2) -> float:
    """What a decode step's cache reads have to move at least: each pool's
    distinct live pages, once for every layer that READS the pool. The
    full pool holds one layer's rows and the full layer and every cross
    layer read them."""
    full_readers = len(d.layers_of("full", "cross"))
    return kv_page_bytes(d, page_size, itemsize) * (
        full_readers * full_pages + len(d.layers_of("window"))
        * window_pages)


def ssm_layer_weight_bytes(d, itemsize: int = 2) -> float:
    """One state-space layer's mixer: the four products' weights and the
    small float32 and convolution parameters."""
    di, ds, dr = d.d_inner, d.d_state, d.dt_rank
    products = d.dim * 2 * di + di * (dr + 2 * ds) + dr * di + di * d.dim
    return itemsize * (products + (d.d_conv + 2) * di) \
        + 4.0 * (di * ds + di)


def gmu_layer_weight_bytes(d, itemsize: int = 2) -> float:
    """One gated memory unit: its two products' weights."""
    return 2.0 * d.dim * d.d_inner * itemsize


def ssm_state_bytes(d, slots: int, itemsize: int = 2) -> float:
    """Every slot's state of ONE state-space layer: the float32 state and
    the convolution's tail."""
    return slots * (4.0 * d.d_inner * d.d_state
                    + itemsize * (d.d_conv - 1) * d.d_inner)


def ssm_step_bytes(d, slots: int, itemsize: int = 2) -> float:
    """What a decode step's recurrent mixers have to move at least: the
    state-space and memory-unit layers' weights once, and every slot's
    state read and written."""
    n_ssm, n_gmu = len(d.layers_of("ssm")), len(d.layers_of("gmu"))
    return n_ssm * (ssm_layer_weight_bytes(d, itemsize)
                    + 2.0 * ssm_state_bytes(d, slots, itemsize)) \
        + n_gmu * gmu_layer_weight_bytes(d, itemsize)

"""Bytes that the algorithm needs, from shapes alone: what this family's
readers name (``metrics/moe_experts_roofline.py``,
``metrics/gqa_read_roofline.py``). Both products they bound are
memory-bound at a decode step's few dozen rows, so only bytes are here."""

from __future__ import annotations


def expert_bytes(d, itemsize: int = 2) -> float:
    """One routed expert's weights: gate, up and down."""
    return 3.0 * d.dim * d.expert_hidden * itemsize


def kv_page_bytes(d, page_size: int, itemsize: int = 2) -> float:
    """One page of ONE layer: ``page_size`` rows of K and of V for every
    key/value head."""
    return 2.0 * d.kv_heads * page_size * d.head_dim * itemsize


def gqa_read_bytes(d, full_pages: float, window_pages: float,
                   page_size: int, itemsize: int = 2) -> float:
    """What a decode step's cache reads have to move at least: each pool's
    distinct live pages, once, in every layer of its type."""
    return kv_page_bytes(d, page_size, itemsize) * (
        d.full_layers * full_pages + d.window_layers * window_pages)

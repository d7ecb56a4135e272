"""Bytes that the algorithm needs, from shapes alone: what this family's
readers name (``metrics/moe_experts_roofline.py``,
``metrics/gqa_read_roofline.py``). Only bytes are here, because both
readers take a kernel's time over its BANDWIDTH floor: the touched experts'
weights read once, the live pages read once. The cache reads are
memory-bound. The held experts' products are not, as the cell runs them:
its 64 slots x 8 picks are 512 pair rows, 32 of them held, and the grouped
product multiplies all 512 against every touched group's weights, which is
compute-bound (``PERF.md`` Open questions ax: ``moe_experts_roofline`` 38.7
on the chip, PR 40). The share says how far the kernel is from what the
ALGORITHM needs, which handing it the held rows alone would close."""

from __future__ import annotations


def expert_bytes(d, itemsize: int = 2) -> float:
    """One routed expert's weights: gate, up and down."""
    return 3.0 * d.dim * d.expert_hidden * itemsize


def kv_page_bytes(d, page_size: int, full: bool, itemsize: int = 2) -> float:
    """One page of ONE layer of one type: ``page_size`` rows of K
    (``head_dim`` a key/value head) and of V (``v_head_dim``), for the
    key/value heads of that layer type."""
    return float(d.kv_heads_of(full) * page_size
                 * (d.head_dim + d.v_head_dim) * itemsize)


def gqa_read_bytes(d, full_pages: float, window_pages: float,
                   page_size: int, itemsize: int = 2) -> float:
    """What a decode step's cache reads have to move at least: each pool's
    distinct live pages, once, in every layer of its type, a page at its
    pool's OWN K and V widths."""
    return (d.full_layers * full_pages
            * kv_page_bytes(d, page_size, True, itemsize)
            + d.window_layers * window_pages
            * kv_page_bytes(d, page_size, False, itemsize))

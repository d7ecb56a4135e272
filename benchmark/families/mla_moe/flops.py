"""Bytes that the algorithm needs, from shapes alone: what this family's
readers name (``metrics/moe_experts_roofline.py``,
``metrics/latent_read_roofline.py``). Both products they bound are
memory-bound at a decode step's few dozen rows, so only bytes are here."""

from __future__ import annotations


def expert_bytes(d, itemsize: int = 2) -> float:
    """One routed expert's weights: gate, up and down."""
    return 3.0 * d.dim * d.expert_hidden * itemsize


def latent_row_bytes(d, itemsize: int = 2) -> float:
    """What a token caches a layer: the latent and the roped key."""
    return float(d.entry_width * itemsize)

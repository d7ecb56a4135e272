"""Bytes that the algorithm needs, from shapes alone: what this family's
readers name (``metrics/moe_experts_roofline.py``,
``metrics/shortconv_step_roofline.py``). Both products they bound are
memory-bound at a decode step's few dozen rows (256 pair rows over 64
experts are 4 rows a group), so only bytes are here."""

from __future__ import annotations


def expert_bytes(d, itemsize: int = 2) -> float:
    """One routed expert's weights: gate, up and down."""
    return 3.0 * d.dim * d.expert_hidden * itemsize


def shortconv_layer_weight_bytes(d, itemsize: int = 2) -> float:
    """One short-convolution mixer: the input projection to three streams,
    the output projection and a weight a tap a channel."""
    return float(itemsize * (3 * d.dim * d.dim + d.dim * d.dim
                             + d.conv_taps * d.dim))


def shortconv_tail_bytes(d, slots: int, itemsize: int = 2) -> float:
    """Every slot's tail of ONE short-convolution layer: the last
    ``conv_taps - 1`` gated inputs."""
    return float(slots * (d.conv_taps - 1) * d.dim * itemsize)


def shortconv_step_bytes(d, slots: int, itemsize: int = 2) -> float:
    """What a decode step's short convolutions have to move at least: each
    layer's weights once, and every slot's tail read and written."""
    return d.conv_layers * (shortconv_layer_weight_bytes(d, itemsize)
                            + 2.0 * shortconv_tail_bytes(d, slots, itemsize))

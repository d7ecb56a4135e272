"""Bytes that the algorithm needs, from shapes alone: what this family's
readers name (``metrics/moe_experts_roofline.py``,
``metrics/delta_step_roofline.py``). Both bounds are of memory: a decode
step's 640 pair rows over 128 held experts are 1.25 rows a group, and a
delta-rule step makes two operations a number of the state it reads and
writes."""

from __future__ import annotations


def expert_bytes(d, itemsize: int = 2) -> float:
    """One routed expert's weights: gate, up and down."""
    return 3.0 * d.dim * d.expert_hidden * itemsize


def delta_layer_weight_bytes(d, itemsize: int = 2) -> float:
    """One delta-rule mixer: the input projection to the four streams q,
    k, v, z, the projection to b and a, the output projection and a
    weight a tap a channel, in the stored type; ``a_log``, ``dt_bias``
    (float32, a value head each) and the gated norm's gain."""
    stored = d.dim * (d.conv_dim + d.value_dim) + d.dim * 2 * d.value_heads \
        + d.value_dim * d.dim + d.conv_taps * d.conv_dim + d.value_head_dim
    return float(itemsize * stored + 4 * 2 * d.value_heads)


def delta_state_bytes(d, slots: int, itemsize: int = 2) -> float:
    """What every slot carries of ONE delta-rule layer: a float32 matrix
    state a value head, and the convolution's last ``conv_taps - 1``
    inputs in the pool's type."""
    return float(slots * (
        4 * d.value_heads * d.key_head_dim * d.value_head_dim
        + itemsize * (d.conv_taps - 1) * d.conv_dim))


def delta_step_bytes(d, slots: int, itemsize: int = 2) -> float:
    """What a decode step's delta-rule layers have to move at least: each
    layer's weights once, and every slot's state and tail read and
    written."""
    return d.delta_layers * (delta_layer_weight_bytes(d, itemsize)
                             + 2.0 * delta_state_bytes(d, slots, itemsize))

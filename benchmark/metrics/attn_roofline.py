"""The flash-attention forward kernel's share of its roofline: the least
time the chip could take for the dense layers' causal attention of one
step's kernel calls (``benchmark/flops.py``: the larger of operations
over peak and bytes over bandwidth; compute bounds it at these shapes)
over their measured device time."""

import os

from benchmark import flops, harness

_kernel = harness.load_metric_module("attn_kernel_ms",
                                     os.path.dirname(__file__))


def read(ctx):
    red = ctx["trace"]
    if red is None or ctx["kind"] != "train":
        return None
    seconds, calls = _kernel.per_step(red)
    if not seconds or not calls:
        return None
    d = ctx["dims"]
    rows = ctx["readings"]["tokens_per_reading"] \
        // ctx["readings"]["steps_per_reading"] // d.seq_len // ctx["chips"]
    # every call is one dense layer's forward over this chip's rows
    least, _bound = flops.roofline_seconds(
        calls * flops.flash_forward_flops(d, rows),
        calls * flops.flash_forward_bytes(d, rows), ctx["peaks"])
    return 100.0 * least / seconds

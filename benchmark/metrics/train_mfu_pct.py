"""Model FLOP/s utilization: the operations one token needs forward and
backward (``benchmark/flops.py``; recomputation does not count) times
tokens per second, over chips times the published bf16 peak."""

from benchmark import flops


def read(ctx):
    rate = ctx["end_to_end"].get("train_tokens_per_s")
    if rate is None:
        return None
    peak = ctx["chips"] * ctx["peaks"]["bf16_flops"]
    return 100.0 * flops.train_flops_per_token(ctx["dims"]) * rate / peak

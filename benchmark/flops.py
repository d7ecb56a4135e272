"""Operations and bytes that the algorithm needs, from shapes alone.

Copied in substance from ``bench.py`` (``dalle_train_flops_per_token``,
``decode_roofline_ms_per_token``; the originals are listed for deletion in
PERF.md, Open questions), with one correction: attention is counted as
the causal or windowed work that is required, not the full square."""

from __future__ import annotations


def attended_keys_mean(d, sparse: bool) -> float:
    """Mean number of keys a query reads over a whole sequence."""
    n = d.seq_len
    if not sparse:
        return (n + 1) / 2.0
    window = d.sparse_block * d.sparse_local_blocks
    return (window + 1) / 2.0 + d.sparse_block   # own window + global block


def train_flops_per_token(d) -> float:
    """Matmul and attention operations for one token, forward and
    backward (3 x forward). Recomputed operations do not count."""
    per_layer = 2 * (d.dim * 3 * d.inner + d.inner * d.dim
                     + d.dim * 2 * d.hidden + d.hidden * d.dim)
    attn = sum(2 * 2 * attended_keys_mean(d, s) * d.inner
               for s in d.sparse_layers)
    head = 2 * d.dim * d.total_tokens
    return 3.0 * (d.depth * per_layer + attn + head)


def flash_forward_flops(d, rows: int) -> float:
    """QK^T and AV of one causal dense attention call, forward."""
    return rows * d.seq_len * 2 * 2 * attended_keys_mean(d, False) * d.inner


def flash_forward_bytes(d, rows: int, itemsize: int = 2) -> float:
    """q, k, v read once and the output written once."""
    return 4.0 * rows * d.seq_len * d.inner * itemsize


def roofline_seconds(flops: float, nbytes: float, peaks: dict):
    """-> (least seconds, which bound: 'compute' or 'memory')."""
    tc, tm = flops / peaks["bf16_flops"], nbytes / peaks["hbm_bytes_per_s"]
    return (tc, "compute") if tc >= tm else (tm, "memory")


def decode_step_bytes(d, slots: int, itemsize: int = 2) -> float:
    """Bytes one decode step must stream: every matmul weight once and
    each slot's K and V at full length."""
    per_layer = (d.dim * 3 * d.inner + d.inner * d.dim
                 + d.dim * 2 * d.hidden + d.hidden * d.dim + 4 * d.dim)
    weights = (d.depth * per_layer + d.dim * d.total_tokens) * itemsize
    kv = slots * 2 * d.depth * d.seq_len * d.inner * itemsize
    return float(weights + kv)

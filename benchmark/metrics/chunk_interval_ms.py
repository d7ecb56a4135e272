"""The decode step as the engine sees it over the whole window, in
milliseconds: the median chunk that ran behind no admission
(``admit_stream_stall_ms.chunk_medians``: a chunk's time is the median of
its ``decode_chunk`` spans, harvest to harvest) over ``chunk_steps``. It
stands beside ``decode_step_device_ms`` (five seconds of capture, the
device's side) and ``tpot_ms`` (the clients' side, admissions in its
median's sample)."""

import os
import statistics

from benchmark import harness


def read(ctx):
    got = harness.load_metric_module(
        "admit_stream_stall_ms", os.path.dirname(__file__)).chunk_medians(ctx)
    if not got or not got[False]:
        return None
    steps = int(ctx["cell"].spec["engine"]["chunk_steps"])
    return 1e3 * statistics.median(got[False]) / steps

"""Continuous-batching inference: slot-pool engine, admission queue,
pipelined postprocess, threaded server (docs/SERVING.md).

Import surface kept lazy-friendly: ``scheduler`` pulls no jax, so queue
types (Request/Result/QueueFull) are importable before a backend exists —
the same discipline as ``resilience`` (utils/metrics.py note)."""

from dalle_pytorch_tpu.serve.auth import (  # noqa: F401
    check_http, check_token, http_token)
from dalle_pytorch_tpu.serve.kv_pool import (  # noqa: F401
    PageAllocator, PagePoolExhausted, PageReleaseUnderflow, pages_for)
from dalle_pytorch_tpu.serve.prefix_cache import (  # noqa: F401
    PrefixEntry, PrefixIndex, content_key, prefix_key)
from dalle_pytorch_tpu.serve.scheduler import (  # noqa: F401
    CANCELLED, DEADLINE_EXCEEDED, ERROR, OK, REJECTED, InvalidRequest,
    QueueClosed, QueueFull, Request, RequestHandle, RequestQueue, Result,
    SamplingParams, ServeRejected, WeightedFairQueue, bucket_for,
    group_by_bucket, prefill_buckets, prefill_groups)
from dalle_pytorch_tpu.serve.fanout import (  # noqa: F401
    GroupFuture, group_pages_saved, rank_samples, sample_seed,
    submit_group)
from dalle_pytorch_tpu.serve.stream import (  # noqa: F401
    TokenSink, sse_bytes, unpack_image)
from dalle_pytorch_tpu.serve.tenancy import (  # noqa: F401
    TIERS, AuthError, TenantSpec, TenantTable, TenantThrottled,
    TokenBucket)


def __getattr__(name):
    # Engine / PostProcessor / InferenceServer import jax at construction;
    # defer the module imports so `from dalle_pytorch_tpu import serve`
    # stays cheap for callers that only need the queue types.
    if name == "Engine":
        from dalle_pytorch_tpu.serve.engine import Engine
        return Engine
    if name in ("ReplicaSet", "ScaleError", "UpgradeAborted",
                "ReplayVersionMismatch"):
        from dalle_pytorch_tpu.serve import replica
        return getattr(replica, name)
    if name in ("Autoscaler", "AutoscalePolicy"):
        from dalle_pytorch_tpu.serve import autoscale
        return getattr(autoscale, name)
    if name == "MeshEngine":
        from dalle_pytorch_tpu.serve.mesh_engine import MeshEngine
        return MeshEngine
    if name == "PostProcessor":
        from dalle_pytorch_tpu.serve.postprocess import PostProcessor
        return PostProcessor
    if name in ("InferenceServer", "make_http_server", "serve_http"):
        from dalle_pytorch_tpu.serve import server
        return getattr(server, name)
    if name in ("Gateway", "Cell", "make_gateway_http_server",
                "serve_gateway_http"):
        # gateway.py itself is jax-free, but it imports the faults /
        # obs stack — defer it with the heavy modules anyway
        from dalle_pytorch_tpu.serve import gateway
        return getattr(gateway, name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")

"""The tiny model of the serving tests, written once: the configuration
(total_len 24), the weights, six requests, the one-shot reference stream
that every engine path must reproduce token for token, and the fixtures.
A helper module, not a test file; the classic serving files
(tests/test_serve.py, test_prefix_cache.py, test_speculative.py,
test_migration.py, test_sparse_reads.py, test_mesh_engine.py, test_obs.py,
test_fanout.py) and the helpers of the split ones (tests/paged_pool.py,
tests/replica_set.py) take what they share from here. A file whose model
differs defines its own ``CFG``: ``bundle`` builds the asking module's."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dalle_pytorch_tpu.models import dalle as D
from dalle_pytorch_tpu.models import vae as V
from dalle_pytorch_tpu.resilience import faults
from dalle_pytorch_tpu.resilience.retry import RetryPolicy
from dalle_pytorch_tpu.serve import Request, SamplingParams

VCFG = V.VAEConfig(image_size=16, num_tokens=32, codebook_dim=16,
                   num_layers=2, hidden_dim=8)
CFG = D.DALLEConfig(dim=16, depth=2, vae=VCFG, num_text_tokens=50,
                    text_seq_len=8, heads=2, dim_head=8)

# short first-retry backoff so circuit-breaker tests run in milliseconds
FAST_BRINGUP = RetryPolicy(max_attempts=1, deadline_s=None,
                           base_backoff_s=0.01, backoff_multiplier=2.0,
                           max_backoff_s=0.1, jitter=0.0)

MORE_REQS = [
    Request(codes=(3, 7, 9), seed=11),
    Request(codes=(5, 2, 8, 1, 4), seed=23,
            sampling=SamplingParams(temperature=0.7, filter_thres=0.8)),
    Request(codes=(6, 6), seed=5,
            sampling=SamplingParams(temperature=1.3, top_p=0.9)),
    Request(codes=(2, 4, 4), seed=7),
    Request(codes=(1, 5), seed=13),
    Request(codes=(4, 4, 4, 4), seed=17),
]
REQS = MORE_REQS[:3]


@pytest.fixture(scope="module")
def bundle(request):
    """(params, vae_params) of the asking module's ``CFG`` (this one's
    where it has none)."""
    cfg = getattr(request.module, "CFG", CFG)
    key = jax.random.PRNGKey(0)
    vae_params = V.vae_init(jax.random.fold_in(key, 1), cfg.vae)
    return D.dalle_init(key, cfg, vae_params), vae_params


@pytest.fixture(autouse=True)
def _no_leaked_plan():
    faults.deactivate()
    yield
    faults.deactivate()


_REF_CACHE: dict = {}


def reference_tokens(params, vae_params, req: Request,
                     quantize_cache: bool = False, cfg=CFG) -> np.ndarray:
    """generate_images at batch 1 (``guidance=req.cfg_scale``): the
    undisturbed one-shot stream of the same seed. Memoized on the weights
    (an upgrade test compares a weight generation at a time), the
    configuration and the request's sampling identity: many tests check
    the same requests, and an uncached call costs a generate_images
    run."""
    key = (id(params), cfg, req.codes, req.seed, req.sampling.temperature,
           req.sampling.filter_thres, req.sampling.top_p, req.cfg_scale,
           quantize_cache)
    if key not in _REF_CACHE:
        _, img_seq = D.generate_images(
            params, vae_params, jnp.asarray([req.codes], jnp.int32), cfg=cfg,
            rng=jax.random.PRNGKey(req.seed),
            filter_thres=req.sampling.filter_thres,
            top_p=req.sampling.top_p,
            temperature=req.sampling.temperature, guidance=req.cfg_scale,
            quantize_cache=quantize_cache, return_img_seq=True)
        _REF_CACHE[key] = np.asarray(img_seq)[0]
    return _REF_CACHE[key]

"""The analytic baseline/roofline estimators behind every ``vs_baseline``
field (VERDICT r4 item 8: no config may emit a null). The constants are
estimates, but the FORMULAS are checked: the generalized A100 estimator
must reproduce the historical 2.9e5 north constant, the sparse count
must charge attention only to dense layers, and the decode roofline must
track the quant arithmetic in ops/quant.py.
"""

import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import bench  # noqa: E402


@pytest.fixture(scope="module")
def north_cfg():
    return bench.build_cfg(False)


def test_a100_estimator_reproduces_north_constant(north_cfg):
    """2.9e5 was hand-derived as 40% of 312 TFLOPs over ~433 MFLOP/token;
    the generalized function must land on the same number (1%)."""
    est = bench.a100_tokens_per_sec_est(north_cfg)
    assert est == pytest.approx(bench.A100_TOKENS_PER_SEC_EST, rel=0.01)


def test_sparse_attention_charged_to_dense_layers_only():
    """The depth-64 (True, False)*32 config must count attention FLOPs on
    the 32 dense layers only — making the A100 estimate FASTER and our
    vs_baseline lower (conservative)."""
    dense = bench.build_cfg(False, depth=64)
    sparse = bench.build_cfg(False, depth=64, sparse=True)
    f_dense = bench.dalle_train_flops_per_token(dense)
    f_sparse = bench.dalle_train_flops_per_token(sparse)
    assert f_sparse < f_dense
    # exactly half the attention term: 32 of 64 layers are sparse
    dh = dense.heads * dense.dim_head
    attn_term = 3.0 * 32 * 2 * (2 * dense.seq_len * dh)
    assert f_dense - f_sparse == pytest.approx(attn_term, rel=1e-9)
    assert bench.a100_tokens_per_sec_est(sparse) \
        > bench.a100_tokens_per_sec_est(dense)


def test_vae_flops_scale_with_resolution():
    from dalle_pytorch_tpu.models import vae as V
    small = V.VAEConfig(image_size=128, num_tokens=2048, codebook_dim=256,
                        num_layers=3, hidden_dim=128)
    big = V.VAEConfig(image_size=256, num_tokens=2048, codebook_dim=256,
                      num_layers=3, hidden_dim=128)
    r = bench.vae_train_flops_per_image(big) \
        / bench.vae_train_flops_per_image(small)
    # conv cost is ~quadratic in resolution (the 1x1 heads dilute it a bit)
    assert 3.0 < r < 4.5
    assert bench.a100_images_per_sec_est(big) \
        < bench.a100_images_per_sec_est(small)


def test_decode_roofline_matches_quant_arithmetic(north_cfg):
    """ops/quant.py:5-13 argues ~113 MB of bf16 weights/token ~= 0.14 ms
    at v5e bandwidth and int8 halves the weight share. The roofline
    function is that arithmetic finished (streamed weights + KV cache;
    embedding gathers excluded): bf16 floor ~ 0.18 ms, int8 strictly
    cheaper but > half (cache stays bf16)."""
    from dalle_pytorch_tpu.utils.device import chip_peaks
    bw = chip_peaks("TPU v5 lite")["hbm_bytes_per_s"]
    bf16 = bench.decode_roofline_ms_per_token(north_cfg,
                                              hbm_bytes_per_s=bw)
    int8 = bench.decode_roofline_ms_per_token(north_cfg, quantize="int8",
                                              hbm_bytes_per_s=bw)
    assert 0.15 < bf16 < 0.25
    assert int8 < bf16
    assert int8 > bf16 / 2          # the KV cache doesn't quantize
    # a batched step amortizes weights but multiplies KV reads: the floor
    # must grow with batch, sublinearly
    b4 = bench.decode_roofline_ms_per_token(north_cfg, batch=4,
                                            hbm_bytes_per_s=bw)
    assert bf16 < b4 < 4 * bf16


def test_peaks_keyed_by_device_kind_with_source():
    """Known ``device_kind`` -> published peaks WITH their source; an
    unknown device is the typed error, never another chip's numbers."""
    from dalle_pytorch_tpu.utils import device
    v5e = device.chip_peaks("TPU v5 lite")
    assert v5e["bf16_flops"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9
    assert "TPU v5e" in v5e["source"]
    for peaks in device.CHIP_PEAKS.values():
        assert peaks["source"]
    with pytest.raises(device.UnknownDeviceError) as ei:
        device.chip_peaks("cpu")
    assert ei.value.device_kind == "cpu"
    # the bench's own lookup goes through the same table: on the CPU test
    # backend it must refuse, not default to v5e
    with pytest.raises(device.UnknownDeviceError):
        bench._bf16_peak()


def test_vs_baseline_fields_emitted_on_tiny_cpu_bench():
    """--tiny --config vae,sparse on CPU: the records must carry numeric
    vs_baseline (the whole point of item 8: no nulls anywhere)."""
    import json
    import subprocess
    # strip the conftest's 8-device forcing: the tiny vae batch (4) must
    # divide the dp mesh, and this test wants the plain single-device path
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "XLA_FLAGS": ""}
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py"), "--tiny",
         "--config", "vae", "--steps", "2", "--warmup", "1"],
        capture_output=True, text=True, timeout=300, env=env)
    assert proc.returncode == 0, proc.stderr[-2000:]
    d = json.loads(proc.stdout.strip().splitlines()[-1])
    assert isinstance(d["vs_baseline"], float)

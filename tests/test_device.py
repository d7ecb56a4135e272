"""utils/device.py: the compile cache is placed from outside or at one
fixed path (never a temporary name), a chip's peaks come by its
``device_kind`` or not at all, and a TPU host refuses process isolation
with a typed error instead of dying in libtpu's lock."""

import os
import re

import jax
import pytest

from dalle_pytorch_tpu.utils import device

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class TestCompileCache:
    def test_outside_variable_is_left_alone(self, monkeypatch):
        """JAX_COMPILATION_CACHE_DIR set: the environment places the
        cache (jax reads the variable itself) — nothing changes in code."""
        before = jax.config.jax_compilation_cache_dir
        monkeypatch.setenv(device.CACHE_ENV, "/somewhere/else")
        assert device.enable_compile_cache() == "/somewhere/else"
        assert jax.config.jax_compilation_cache_dir == before

    def test_fixed_path_when_unset(self, monkeypatch):
        monkeypatch.delenv(device.CACHE_ENV, raising=False)
        before = jax.config.jax_compilation_cache_dir
        try:
            path = device.enable_compile_cache()
            assert path == os.path.join(REPO, ".jax_cache")
            assert jax.config.jax_compilation_cache_dir == path
            # fixed: a second call (another process of the same command)
            # lands on the same directory
            assert device.enable_compile_cache() == path
        finally:
            jax.config.update("jax_compilation_cache_dir", before)

    def test_suite_cache_goes_through_the_helper(self):
        """tests/conftest.py honours an outside variable, else the fixed
        directory — never a mkdtemp name."""
        want = os.environ.get(device.CACHE_ENV) or device.default_cache_dir()
        assert jax.config.jax_compilation_cache_dir == want
        assert "tmp" not in os.path.basename(want)

    def test_one_writer_and_no_temporary_names(self):
        """Only the helper writes ``jax_compilation_cache_dir``, and no
        cache path in the tree is built from mkdtemp, a pid or the time."""
        writers, suspects = [], []
        skip = {os.path.join(REPO, "tests", "test_device.py")}
        for root, dirs, files in os.walk(REPO):
            dirs[:] = [d for d in dirs if not d.startswith(".")
                       and d not in ("chiprun_out", "__pycache__")]
            for name in files:
                path = os.path.join(root, name)
                if not name.endswith((".py", ".sh")) or path in skip:
                    continue
                with open(path, errors="replace") as f:
                    text = f.read()
                if re.search(r"update\(\s*[\"']jax_compilation_cache_dir",
                             text):
                    writers.append(os.path.relpath(path, REPO))
                for line in text.splitlines():
                    if re.search(r"(?i)jax_?cache|compilation_cache", line) \
                            and re.search(r"mkdtemp|getpid|time\.time",
                                          line):
                        suspects.append((os.path.relpath(path, REPO), line))
        assert writers == [os.path.join("dalle_pytorch_tpu", "utils",
                                        "device.py")]
        assert suspects == []


def test_peaks_keyed_by_device_kind_with_source():
    """Known ``device_kind`` -> published peaks WITH their source; an
    unknown device is the typed error, never another chip's numbers."""
    v5e = device.chip_peaks("TPU v5 lite")
    assert v5e["bf16_flops"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9
    assert "TPU v5e" in v5e["source"]
    for peaks in device.CHIP_PEAKS.values():
        assert peaks["source"]
    with pytest.raises(device.UnknownDeviceError) as ei:
        device.chip_peaks("cpu")
    assert ei.value.device_kind == "cpu"


class TestChipOwnership:
    """isolation='process' with locally spawned workers on a TPU host is a
    typed start-up refusal: the parent already holds the chips."""

    @pytest.fixture
    def on_tpu(self, monkeypatch):
        monkeypatch.setattr(
            device, "describe_device",
            lambda: {"platform": "tpu", "kind": "TPU v5 lite", "count": 4})

    def test_refused_before_anything_spawns(self, on_tpu):
        from dalle_pytorch_tpu.serve import RequestQueue
        from dalle_pytorch_tpu.serve.replica import (ChipOwnershipError,
                                                     ReplicaSet)
        for kw in ({"transport": "pipe"}, {"transport": "socket"}):
            with pytest.raises(ChipOwnershipError) as ei:
                # params/cfg are never touched: the refusal comes first
                ReplicaSet(None, None, RequestQueue(max_depth=4),
                           replicas=2, isolation="process", **kw)
            rec = ei.value.record
            assert rec["kind"] == "serve_isolation_unsupported"
            assert rec["platform"] == "tpu" and rec["device_count"] == 4
            assert "one process" in str(ei.value)

    def test_thread_mode_and_remote_workers_pass(self, on_tpu):
        from dalle_pytorch_tpu.serve.replica import check_chip_ownership
        check_chip_ownership("thread", None)
        # workers an operator starts on OTHER hosts own their own chips
        check_chip_ownership("process", "")
        check_chip_ownership("process", "ssh host python -m worker")

    def test_cpu_hosts_keep_process_isolation(self):
        from dalle_pytorch_tpu.serve.replica import check_chip_ownership
        check_chip_ownership("process", None)       # this backend: cpu

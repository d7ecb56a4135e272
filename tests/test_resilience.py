"""Resilience runtime unit tests: deadline/backoff/jitter bring-up,
supervisor rollback/re-warm/preemption mechanics, checkpoint-validation
driven auto-resume discovery, data-path fault handling, and the
pp_param_specs ep guard (docs/RESILIENCE.md)."""

import os
import random
import signal
import threading
import time

import numpy as np
import pytest

from dalle_pytorch_tpu import checkpoint as ckpt
from dalle_pytorch_tpu.data import Prefetcher, prefetch
from dalle_pytorch_tpu.resilience import (BringupError, DeadlineExceeded,
                                          Preempted, RetryPolicy,
                                          TrainingDiverged, TrainSupervisor,
                                          call_with_deadline, faults,
                                          find_auto_resume,
                                          retry_with_backoff)

pytestmark = pytest.mark.faults


@pytest.fixture(autouse=True)
def _no_leaked_plan():
    faults.deactivate()
    yield
    faults.deactivate()


# ---------------------------------------------------------------------------
# retry: deadline + exponential backoff + jitter
# ---------------------------------------------------------------------------

class TestRetry:
    def test_deadline_returns_result_and_reraises(self):
        assert call_with_deadline(lambda: 42, 5.0, "t") == 42
        with pytest.raises(ValueError, match="boom"):
            call_with_deadline(lambda: (_ for _ in ()).throw(
                ValueError("boom")), 5.0, "t")

    def test_deadline_fires_instead_of_hanging(self):
        t0 = time.monotonic()
        with pytest.raises(DeadlineExceeded):
            call_with_deadline(lambda: time.sleep(30), 0.15, "wedged")
        assert time.monotonic() - t0 < 5.0     # nowhere near the 30 s hang

    def test_backoff_is_exponential_then_capped(self):
        p = RetryPolicy(base_backoff_s=1.0, backoff_multiplier=2.0,
                        max_backoff_s=5.0, jitter=0.0)
        assert [p.backoff(a) for a in range(4)] == [1.0, 2.0, 4.0, 5.0]

    def test_backoff_jitter_bounded_and_seeded(self):
        p = RetryPolicy(base_backoff_s=10.0, jitter=0.25)
        rng = random.Random(0)
        draws = [p.backoff(0, rng) for _ in range(50)]
        assert all(7.5 <= d <= 12.5 for d in draws)
        assert len(set(draws)) > 1             # actually jittered
        assert draws == [RetryPolicy(base_backoff_s=10.0, jitter=0.25)
                         .backoff(0, random.Random(0))
                         for _ in range(1)] + draws[1:]  # deterministic rng

    def test_retries_then_recovers_with_events(self):
        calls, events = [], []

        def flaky(attempt):
            calls.append(attempt)
            if attempt < 2:
                raise RuntimeError(f"fail {attempt}")
            return "ok"

        out = retry_with_backoff(
            flaky, RetryPolicy(max_attempts=3, deadline_s=5.0,
                               base_backoff_s=0.01, jitter=0.0),
            label="t", on_event=events.append)
        assert out == "ok" and calls == [0, 1, 2]
        assert [e["kind"] for e in events] == ["bringup_retry"] * 2
        assert events[0]["attempt"] == 1 and "fail 0" in events[0]["error"]

    def test_exhaustion_raises_structured_record(self):
        events = []
        with pytest.raises(BringupError) as ei:
            retry_with_backoff(
                lambda a: (_ for _ in ()).throw(RuntimeError(f"e{a}")),
                RetryPolicy(max_attempts=2, deadline_s=5.0,
                            base_backoff_s=0.01, jitter=0.0),
                label="claim", on_event=events.append)
        rec = ei.value.record
        assert rec["event"] == "resilience"
        assert rec["kind"] == "bringup_failure"
        assert rec["label"] == "claim" and rec["attempts"] == 2
        assert len(rec["errors"]) == 2 and "e1" in rec["errors"][-1]
        assert events[-1] == rec               # terminal record emitted too


# ---------------------------------------------------------------------------
# wedged backend init: injected timeout -> retries -> structured failure,
# never a hang (acceptance criterion)
# ---------------------------------------------------------------------------

class TestBackendBringup:
    def test_multihost_init_wedged_surfaces_record(self, monkeypatch):
        from dalle_pytorch_tpu.parallel import multihost
        monkeypatch.setattr(multihost, "_initialized", False)
        events = []
        t0 = time.monotonic()
        with faults.injected(backend_init_hang_s=30):
            with pytest.raises(BringupError) as ei:
                multihost.initialize(coordinator_address="127.0.0.1:1",
                                     num_processes=1, process_id=0,
                                     deadline_s=0.15, max_attempts=2,
                                     on_event=events.append)
        assert time.monotonic() - t0 < 15.0    # both attempts deadline-cut
        rec = ei.value.record
        assert rec["kind"] == "bringup_failure"
        assert rec["label"] == "multihost_init" and rec["attempts"] == 2
        assert any(e["kind"] == "bringup_retry" for e in events)
        assert not multihost._initialized      # failure must not mark joined

    def test_multihost_init_injected_failure_no_hang_path(self, monkeypatch):
        from dalle_pytorch_tpu.parallel import multihost
        monkeypatch.setattr(multihost, "_initialized", False)
        with faults.injected(backend_init_fail_attempts=99):
            with pytest.raises(BringupError) as ei:
                multihost.initialize(coordinator_address="127.0.0.1:1",
                                     num_processes=1, process_id=0,
                                     deadline_s=5.0, max_attempts=2)
        assert "injected backend init failure" in ei.value.record[
            "errors"][-1]


# ---------------------------------------------------------------------------
# data path: propagate / skip-with-cap / restart
# ---------------------------------------------------------------------------

class TestPrefetchFaults:
    def test_crashing_iterator_propagates_after_good_batches(self):
        items = [np.full((2,), i, np.float32) for i in range(4)]
        it = prefetch(faults.crashing_iterator(items, 2), depth=1)
        assert int(np.asarray(next(it))[0]) == 0
        assert int(np.asarray(next(it))[0]) == 1
        with pytest.raises(faults.FaultInjected):
            next(it)

    def test_skip_bad_records_counted_with_events(self):
        events = []

        def transform(x):
            if x % 2:
                raise ValueError(f"bad record {x}")
            return np.full((2,), x, np.float32)

        p = Prefetcher(iter(range(6)), transform=transform,
                       max_bad_records=3, on_event=events.append)
        out = [int(np.asarray(b)[0]) for b in p]
        assert out == [0, 2, 4]
        assert p.bad_records == 3
        assert [e["kind"] for e in events] == ["prefetch_bad_record"] * 3
        assert events[0]["cap"] == 3

    def test_source_pos_counts_skipped_records(self):
        """The resume contract: ``source_pos`` after receiving a batch is
        the number of SOURCE records consumed up to and including it —
        bad skipped records included, worker read-ahead excluded — so a
        mid-epoch checkpoint skips exactly the right prefix on resume
        even when --max_bad_records dropped records before the kill."""
        def transform(x):
            if x == 2:
                raise ValueError("bad")
            return np.full((1,), x, np.float32)

        p = Prefetcher(iter(range(5)), transform=transform,
                       max_bad_records=1, depth=1)
        seen, positions = [], []
        for b in p:
            seen.append(int(np.asarray(b)[0]))
            positions.append(p.source_pos)
        assert seen == [0, 1, 3, 4]
        # batch "3" carries position 4: records 0,1,bad-2,3 consumed
        assert positions == [1, 2, 4, 5]

    def test_bad_record_cap_exceeded_propagates(self):
        def transform(x):
            raise ValueError(f"bad {x}")

        p = Prefetcher(iter(range(5)), transform=transform,
                       max_bad_records=2)
        with pytest.raises(ValueError, match="bad 2"):
            list(p)
        assert p.bad_records == 2

    def test_default_still_propagates_without_skipping(self):
        # the pre-existing contract (test_data.py::test_error_propagates):
        # no opt-in, no swallowing
        def gen():
            yield np.zeros((1,))
            raise RuntimeError("boom")

        it = prefetch(gen())
        next(it)
        with pytest.raises(RuntimeError, match="boom"):
            next(it)

    def test_iterator_retry_opt_in(self):
        events = []

        class FlakySource:
            def __init__(self):
                self.i = 0
                self.failed = False

            def __iter__(self):
                return self

            def __next__(self):
                if self.i == 2 and not self.failed:
                    self.failed = True
                    raise OSError("transient read error")
                if self.i >= 4:
                    raise StopIteration
                self.i += 1
                return np.full((2,), self.i, np.float32)

        p = Prefetcher(FlakySource(), iterator_retries=1,
                       on_event=events.append)
        assert [int(np.asarray(b)[0]) for b in p] == [1, 2, 3, 4]
        assert p.iterator_retries == 1
        assert events[0]["kind"] == "prefetch_iterator_retry"

    def test_dead_worker_restarted_once(self):
        events = []

        class DiesOnce(Prefetcher):
            deaths = 0

            def _worker(self):
                if type(self).deaths == 0:
                    type(self).deaths += 1
                    return                     # hard death: NO sentinel
                super()._worker()

        p = DiesOnce(iter([np.full((2,), 7, np.float32)]),
                     on_event=events.append)
        assert int(np.asarray(next(p))[0]) == 7
        assert any(e["kind"] == "prefetch_restart" for e in events)
        with pytest.raises(StopIteration):
            next(p)

    def test_dead_worker_second_death_fails_loudly(self):
        class AlwaysDies(Prefetcher):
            def _worker(self):
                return                         # never a sentinel

        p = AlwaysDies(iter([1]))
        with pytest.raises(RuntimeError, match="died without reporting"):
            next(p)


# ---------------------------------------------------------------------------
# supervisor mechanics
# ---------------------------------------------------------------------------

def _dummy_params():
    return {"w": np.arange(6, dtype=np.float32).reshape(2, 3)}


def _mk_sup(tmp_path, **kw):
    params = _dummy_params()
    saves = []

    def save_state(path):
        saves.append(path)
        return ckpt.save(path, params, step=len(saves))

    sup = TrainSupervisor(name="toy", models_dir=str(tmp_path),
                          save_state=save_state, **kw)
    sup._saves = saves
    return sup


class TestSupervisor:
    def test_nan_without_anchor_diverges(self, tmp_path):
        sup = _mk_sup(tmp_path)
        with pytest.raises(TrainingDiverged, match="no valid checkpoint"):
            sup.check_step(0, float("nan"))

    def test_nan_rolls_back_to_anchor_then_budget_exhausts(self, tmp_path):
        sup = _mk_sup(tmp_path, max_rollbacks=2)
        anchor = ckpt.save(str(tmp_path / "toy-step1"), _dummy_params())
        sup.register_checkpoint(anchor)
        assert sup.check_step(0, 1.0) == sup.OK
        assert sup.check_step(1, float("inf")) == sup.ROLLBACK
        assert sup.rollback_target() == anchor
        assert sup.check_step(2, float("nan")) == sup.ROLLBACK
        with pytest.raises(TrainingDiverged, match="rollback"):
            sup.check_step(3, float("nan"))

    def test_spike_detection_against_median(self, tmp_path):
        sup = _mk_sup(tmp_path, spike_factor=3.0, spike_window=8)
        anchor = ckpt.save(str(tmp_path / "toy-step1"), _dummy_params())
        sup.register_checkpoint(anchor)
        for s in range(6):
            assert sup.check_step(s, 1.0 + 0.01 * s) == sup.OK
        assert sup.check_step(6, 2.5) == sup.OK      # below 3x median
        assert sup.check_step(7, 10.0) == sup.ROLLBACK

    def test_rollback_skips_corrupt_anchor(self, tmp_path):
        sup = _mk_sup(tmp_path)
        good = ckpt.save(str(tmp_path / "toy-step1"), _dummy_params())
        newer = ckpt.save(str(tmp_path / "toy-step2"), _dummy_params())
        sup.register_checkpoint(good)
        sup.register_checkpoint(newer)
        faults.truncate_params(newer)
        assert sup.rollback_target() == good

    def test_rewarm_ramp(self, tmp_path):
        sup = _mk_sup(tmp_path, rewarm_steps=4)
        anchor = ckpt.save(str(tmp_path / "toy-step1"), _dummy_params())
        sup.register_checkpoint(anchor)
        assert sup.lr_scale(5) == 1.0
        assert sup.check_step(10, float("nan")) == sup.ROLLBACK
        assert sup.lr_scale(11) == pytest.approx(1 / 5)
        assert sup.lr_scale(13) == pytest.approx(3 / 5)
        assert sup.lr_scale(15) == 1.0
        assert sup.lr_scale(16) == 1.0           # ramp over, back to normal

    def test_cadence_save_and_retention_gc(self, tmp_path):
        sup = _mk_sup(tmp_path, save_every=1, keep=2)
        for step in range(1, 5):
            sup.end_step(step)
        steps = [s for s, _ in ckpt.step_checkpoints(str(tmp_path), "toy")]
        assert steps == [3, 4]                   # 1, 2 GC'd
        assert sup.rollback_target().endswith("toy-step4")

    def test_preemption_signal_checkpoints_and_unwinds(self, tmp_path):
        sup = _mk_sup(tmp_path).install_signal_handlers()
        try:
            os.kill(os.getpid(), signal.SIGTERM)
            assert sup.preempted
            with pytest.raises(Preempted) as ei:
                sup.end_step(7)
            assert ei.value.path.endswith("toy-step7")
            ok, _ = ckpt.validate(ei.value.path)
            assert ok
        finally:
            sup.close()
        # handlers restored: default disposition again
        assert signal.getsignal(signal.SIGTERM) in (
            signal.SIG_DFL, signal.default_int_handler, signal.SIG_IGN) \
            or not callable(signal.getsignal(signal.SIGTERM)) \
            or signal.getsignal(signal.SIGTERM).__qualname__.find(
                "handler") < 0

    def test_lr_scale_added_to_batch_only_with_rewarm(self, tmp_path):
        sup = _mk_sup(tmp_path, rewarm_steps=0)
        batch = {"x": np.zeros(2)}
        assert "lr_scale" not in sup.pre_step(0, batch)
        sup2 = _mk_sup(tmp_path, rewarm_steps=3)
        out = sup2.pre_step(0, {"x": np.zeros(2)})
        assert float(out["lr_scale"]) == 1.0


# ---------------------------------------------------------------------------
# auto-resume discovery: newest VALID checkpoint by training progress
# ---------------------------------------------------------------------------

class TestFindAutoResume:
    def test_step_ckpt_beats_older_epoch_ckpt(self, tmp_path):
        d = str(tmp_path)
        ckpt.save(ckpt.ckpt_path(d, "vae", 0), _dummy_params(),
                  meta={"epoch": 0, "global_step": 2})
        ckpt.save(ckpt.step_ckpt_path(d, "vae", 3), _dummy_params(),
                  meta={"epoch": 1, "step_in_epoch": 1, "global_step": 3})
        path, manifest = find_auto_resume(d, "vae")
        assert path.endswith("vae-step3")
        assert manifest["meta"]["step_in_epoch"] == 1

    def test_epoch_ckpt_beats_step_ckpt_it_superseded(self, tmp_path):
        d = str(tmp_path)
        ckpt.save(ckpt.step_ckpt_path(d, "vae", 3), _dummy_params(),
                  meta={"epoch": 1, "step_in_epoch": 1, "global_step": 3})
        ckpt.save(ckpt.ckpt_path(d, "vae", 1), _dummy_params(),
                  meta={"epoch": 1, "global_step": 4})
        path, _ = find_auto_resume(d, "vae")
        assert path.endswith("vae-1")

    def test_corrupt_newest_falls_back_to_previous_valid(self, tmp_path):
        d = str(tmp_path)
        ckpt.save(ckpt.ckpt_path(d, "vae", 0), _dummy_params(),
                  meta={"epoch": 0, "global_step": 2})
        bad = ckpt.save(ckpt.step_ckpt_path(d, "vae", 3), _dummy_params(),
                        meta={"epoch": 1, "step_in_epoch": 1,
                              "global_step": 3})
        faults.truncate_params(bad)
        path, _ = find_auto_resume(d, "vae")
        assert path.endswith("vae-0")

    def test_interrupted_save_staging_dir_is_ignored(self, tmp_path):
        d = str(tmp_path)
        ckpt.save(ckpt.ckpt_path(d, "vae", 0), _dummy_params(),
                  meta={"epoch": 0, "global_step": 2})
        faults.simulate_interrupted_save(d)
        path, _ = find_auto_resume(d, "vae")
        assert path.endswith("vae-0")

    def test_empty_dir_returns_none(self, tmp_path):
        assert find_auto_resume(str(tmp_path), "vae") is None


# ---------------------------------------------------------------------------
# satellite: pp_param_specs must not silently drop requested ep sharding
# ---------------------------------------------------------------------------

class TestPPParamSpecsEpGuard:
    def test_ep_without_moe_subtree_raises(self):
        from dalle_pytorch_tpu.parallel import pp_param_specs
        params = {"transformer": {"attn": {"w": np.zeros((2, 4, 4))},
                                  "ff": {"w1": np.zeros((2, 4, 8))}},
                  "emb": {"w": np.zeros((10, 4))}}
        with pytest.raises(ValueError, match="no .*moe.* subtree"):
            pp_param_specs(params, ep="ep")
        # without ep the same tree is fine
        specs = pp_param_specs(params)
        assert specs["emb"]["w"] is not None

    def test_ep_with_moe_subtree_shards_experts(self):
        from jax.sharding import PartitionSpec as P

        from dalle_pytorch_tpu.parallel import pp_param_specs
        params = {"transformer": {
            "attn": {"w": np.zeros((2, 4, 4))},
            "ff": {"moe": {"w1": np.zeros((2, 4, 4, 8)),
                           "w2": np.zeros((2, 4, 8, 4)),
                           "router": {"w": np.zeros((2, 4, 4))}}}}}
        specs = pp_param_specs(params, ep="ep")
        assert specs["transformer"]["ff"]["moe"]["w1"] == P("pp", "ep")
        assert specs["transformer"]["ff"]["moe"]["w2"] == P("pp", "ep")

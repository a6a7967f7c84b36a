"""Chaos matrix for the DESIGN.md §8 resilience layer.

End-to-end scenarios (tiny CHGNet, per-step-seeded batches so an
interrupted run sees the SAME data as an uninterrupted one):

  - SIGTERM preemption: checkpoint + resume marker at the exact step,
    resumed run finishes BIT-identical to an uninterrupted reference;
  - corrupt-newest checkpoint: restore falls back to the next-newest
    valid file; pruning never counts corrupt files against keep-K;
  - NaN-streak divergence: sentinel trips, the run rolls back to the
    last good checkpoint, quarantines the streak's batches, and the
    loss still descends;
  - determinism: the same seed + chaos schedule reproduces the
    identical metric history.

Plus unit coverage of the building blocks: verified checkpoints, the
async writer, the divergence sentinel, Prefetcher retry/shutdown, the
chaos schedule grammar, and the restart allowlist.
"""
import itertools
import os

import jax
import numpy as np
import pytest

from repro.batching import capacity_for
from repro.core.chgnet import CHGNetConfig
from repro.data import (
    BatchIterator, Prefetcher, SyntheticConfig, TaggedBatch,
    TransientSampleError, make_dataset,
)
from repro.runtime import (
    AsyncCheckpointWriter, ChaosMonkey, ChaosSchedule, CheckpointCorruptError,
    DivergenceSentinel, GracefulShutdown, PreemptionError,
    corrupt_newest_checkpoint, latest_step, latest_valid_step,
    list_checkpoints, read_resume_marker, restore_checkpoint,
    run_with_restarts, save_checkpoint, verify_checkpoint,
)
from repro.runtime.checkpoint import _ckpt_path
from repro.train import TrainConfig, Trainer

BATCH = 4


@pytest.fixture(scope="module")
def setup():
    ds = make_dataset(SyntheticConfig(num_crystals=16, max_atoms=10, seed=0))
    return ds, capacity_for(ds, BATCH), CHGNetConfig(dim=16, num_blocks=1)


def _step_batches(ds, caps, start, stop, *, tag=False):
    """Batch for step s is a pure function of s — an interrupted run
    resumed at step k replays the identical data an uninterrupted run saw."""
    for s in range(start, stop):
        it = BatchIterator(ds, BATCH, 1, caps, seed=s, tag_indices=tag)
        yield next(iter(it))


def _tcfg(steps, **kw):
    return TrainConfig(global_batch=BATCH, total_steps=steps, **kw)


# ---------------------------------------------------------------------------
# verified checkpoints
# ---------------------------------------------------------------------------

def _tree(val, n=4096):
    return {"w": np.full(n, val, np.float32),
            "b": np.arange(8, dtype=np.float32) * val}


def test_corrupt_newest_falls_back(tmp_path):
    d = str(tmp_path)
    for step in (1, 2, 3):
        save_checkpoint(d, step, _tree(step), keep=5)
    corrupt_newest_checkpoint(d, mode="truncate")
    assert latest_step(d) == 3  # the file exists ...
    assert latest_valid_step(d) == 2  # ... but is not a restore target
    assert not verify_checkpoint(_ckpt_path(d, 3))
    state, step, _ = restore_checkpoint(d, _tree(0.0))
    assert step == 2
    np.testing.assert_array_equal(state["w"], _tree(2)["w"])


def test_bitflip_detected_by_manifest(tmp_path):
    d = str(tmp_path)
    save_checkpoint(d, 1, _tree(1.0), keep=5)
    corrupt_newest_checkpoint(d, mode="bitflip", seed=0)
    # 4096 floats dominate the payload, so a seeded 8-bit flip lands in
    # array data; the CRC manifest must catch what msgpack can't
    assert not verify_checkpoint(_ckpt_path(d, 1))
    with pytest.raises(CheckpointCorruptError):
        restore_checkpoint(d, _tree(0.0), fallback=False)


def test_explicit_step_restore_never_falls_back(tmp_path):
    d = str(tmp_path)
    for step in (1, 2):
        save_checkpoint(d, step, _tree(step), keep=5)
    corrupt_newest_checkpoint(d, mode="truncate")
    with pytest.raises(CheckpointCorruptError):
        restore_checkpoint(d, _tree(0.0), step=2)


def test_prune_counts_only_valid_checkpoints(tmp_path):
    d = str(tmp_path)
    for step in (1, 2, 3):
        save_checkpoint(d, step, _tree(step), keep=10)
    corrupt_newest_checkpoint(d, mode="truncate")  # step 3 invalid
    # keep=2 over VALID files: 1 and 2 both survive (3 doesn't count)
    save_checkpoint(d, 4, _tree(4), keep=2)
    steps = list_checkpoints(d)
    assert 2 in steps and 4 in steps
    assert latest_valid_step(d) == 4
    assert 1 not in steps  # oldest valid beyond keep-K is gone


def test_all_corrupt_raises(tmp_path):
    d = str(tmp_path)
    save_checkpoint(d, 1, _tree(1.0), keep=5)
    corrupt_newest_checkpoint(d, mode="truncate")
    with pytest.raises(CheckpointCorruptError):
        restore_checkpoint(d, _tree(0.0))


# ---------------------------------------------------------------------------
# async writer
# ---------------------------------------------------------------------------

def test_async_writer_matches_sync_bytes(tmp_path):
    sync_d, async_d = str(tmp_path / "s"), str(tmp_path / "a")
    for step in (1, 2, 3):
        save_checkpoint(sync_d, step, _tree(step), keep=2)
    with AsyncCheckpointWriter(async_d, keep=2) as w:
        for step in (1, 2, 3):
            w.save(step, _tree(step))
        w.flush()
        assert w.last_written_step == 3
        assert w.writes == 3
    assert list_checkpoints(sync_d) == list_checkpoints(async_d) == [2, 3]
    for step in (2, 3):
        a = open(_ckpt_path(sync_d, step), "rb").read()
        b = open(_ckpt_path(async_d, step), "rb").read()
        assert a == b  # same serializer, same bytes: one restore path


def test_async_writer_snapshot_isolation(tmp_path):
    # mutating the tree after save() must not leak into the file
    tree = {"w": np.zeros(16, np.float32)}
    with AsyncCheckpointWriter(str(tmp_path)) as w:
        w.save(1, tree)
        tree["w"] += 999.0
        w.flush()
    state, _, _ = restore_checkpoint(str(tmp_path), {"w": np.zeros(16,
                                                                   np.float32)})
    np.testing.assert_array_equal(state["w"], np.zeros(16, np.float32))


def test_async_writer_surfaces_worker_error(tmp_path):
    blocked = tmp_path / "not_a_dir"
    blocked.write_text("occupied")  # directory path is taken by a file
    w = AsyncCheckpointWriter(str(blocked))
    w.save(1, _tree(1.0))
    with pytest.raises(RuntimeError, match="NOT durable"):
        w.flush()
    w.close()  # error was consumed by flush: close is clean


# ---------------------------------------------------------------------------
# divergence sentinel
# ---------------------------------------------------------------------------

def test_sentinel_nan_streak_trips():
    s = DivergenceSentinel(nan_streak=2)
    assert not s.record(float("nan"))
    assert s.suspicious
    assert s.record(float("nan"))
    assert s.last_trip_len == 2
    assert not s.suspicious  # trip resets the streaks


def test_sentinel_scaler_skipped_exempt():
    s = DivergenceSentinel(nan_streak=1)
    for _ in range(10):
        assert not s.record(float("nan"), scaler_skipped=True)
    assert not s.suspicious


def test_sentinel_spike_streak_trips_and_median_uncontaminated():
    s = DivergenceSentinel(spike_factor=10.0, spike_streak=3, min_history=4)
    for _ in range(8):
        assert not s.record(1.0)
    assert not s.record(50.0)
    assert not s.record(50.0)
    assert s.record(50.0)  # 3rd consecutive spike
    # spikes never entered the reference window: 50x is still a spike
    for _ in range(2):
        assert not s.record(50.0)
    assert s.record(50.0)


def test_sentinel_isolated_spike_no_trip():
    s = DivergenceSentinel(spike_streak=2, min_history=4)
    for _ in range(6):
        s.record(1.0)
    assert not s.record(100.0)
    assert not s.record(1.0)  # streak broken
    assert not s.record(100.0)


# ---------------------------------------------------------------------------
# prefetcher retry / shutdown
# ---------------------------------------------------------------------------

class _FlakySource:
    """Resumable source raising TransientSampleError at given positions."""

    def __init__(self, n, fail_at=(), always_fail=False):
        self.n, self.i = n, 0
        self.fail_at = set(fail_at)
        self.always_fail = always_fail

    def __iter__(self):
        return self

    def __next__(self):
        if self.i >= self.n:
            raise StopIteration
        i = self.i
        self.i += 1
        if self.always_fail or i in self.fail_at:
            raise TransientSampleError(index=i)
        return i


def test_prefetcher_quarantines_transient_and_continues():
    pf = Prefetcher(_FlakySource(6, fail_at={2, 4}), backoff=0.001)
    assert list(pf) == [0, 1, 3, 5]
    assert pf.quarantined == [2, 4]


def test_prefetcher_escalates_after_max_retries():
    pf = Prefetcher(_FlakySource(6, always_fail=True), max_retries=2,
                    backoff=0.001)
    with pytest.raises(TransientSampleError):
        list(pf)


def test_prefetcher_early_break_joins_worker():
    # infinite source + tiny queue: the worker WILL be blocked on put
    pf = Prefetcher(itertools.count(), depth=1)
    for x in pf:
        if x >= 1:
            break  # consumer leaves early; close() runs via finally
    pf.thread.join(5.0)
    assert not pf.thread.is_alive()


def test_prefetcher_worker_crash_reraised_in_consumer():
    def boom():
        yield 1
        raise RuntimeError("worker died")

    pf = Prefetcher(boom())
    with pytest.raises(RuntimeError, match="worker died"):
        list(pf)
    assert not pf.thread.is_alive()


# ---------------------------------------------------------------------------
# chaos schedule / restart allowlist
# ---------------------------------------------------------------------------

def test_chaos_schedule_parse_roundtrip():
    spec = "nan@5,sigterm@12,drop@7:0,straggler@9:0.2"
    sched = ChaosSchedule.parse(spec, seed=3)
    assert sched.spec() == "nan@5,drop@7:0,straggler@9:0.2,sigterm@12"
    assert ChaosSchedule.parse(sched.spec(), seed=3) == sched
    assert [e.kind for e in sched.at(7, frozenset({"drop"}))] == ["drop"]


def test_chaos_schedule_rejects_bad_tokens():
    with pytest.raises(ValueError):
        ChaosSchedule.parse("frobnicate@3")
    with pytest.raises(ValueError):
        ChaosSchedule.parse("nan@notastep")


def test_run_with_restarts_fails_fast_on_programming_errors():
    calls = []

    def loop(start):
        calls.append(start)
        raise ValueError("config typo")

    with pytest.raises(ValueError):
        run_with_restarts(loop, resume_step_fn=lambda: 0, max_restarts=5)
    assert len(calls) == 1  # no doomed retries


@pytest.mark.parametrize("msg", [
    "INTERNAL: Mosaic failed to compile TPU kernel: Slice shape along "
    "dimension 1 must be aligned to tiling (128), but is 1",
    "RESOURCE_EXHAUSTED: Ran out of memory in memory space vmem",
    "RESOURCE_EXHAUSTED: Error allocating device buffer",
])
def test_run_with_restarts_fails_fast_on_compile_and_oom(msg):
    calls = []

    def loop(start):
        calls.append(start)
        raise jax.errors.JaxRuntimeError(msg)

    with pytest.raises(jax.errors.JaxRuntimeError):
        run_with_restarts(loop, resume_step_fn=lambda: 0, max_restarts=5)
    assert len(calls) == 1  # the same program would fail the same way


def test_run_with_restarts_retries_transient_device_errors():
    calls = []

    def loop(start):
        calls.append(start)
        if len(calls) < 3:
            raise jax.errors.JaxRuntimeError("UNAVAILABLE: socket closed")
        return "done"

    assert run_with_restarts(loop, resume_step_fn=lambda: 0,
                             max_restarts=5) == "done"
    assert len(calls) == 3


def test_run_with_restarts_never_retries_preemption():
    calls = []

    def loop(start):
        calls.append(start)
        raise PreemptionError(7)

    with pytest.raises(PreemptionError):
        run_with_restarts(loop, resume_step_fn=lambda: 0, max_restarts=5)
    assert len(calls) == 1


# ---------------------------------------------------------------------------
# end-to-end chaos scenarios
# ---------------------------------------------------------------------------

def test_sigterm_resume_bit_identical(setup, tmp_path):
    ds, caps, cfg = setup
    steps, d = 6, str(tmp_path)
    # uninterrupted reference
    ref = Trainer(cfg, _tcfg(steps))
    ref.train(_step_batches(ds, caps, 0, steps))
    # interrupted at step 3 (real SIGTERM via the chaos monkey)
    monkey = ChaosMonkey(ChaosSchedule.parse("sigterm@3"))
    with GracefulShutdown() as shutdown:
        tr = Trainer(cfg, _tcfg(steps), ckpt_dir=d, ckpt_every=100,
                     shutdown=shutdown)
        with pytest.raises(PreemptionError):
            tr.train(_step_batches(ds, caps, 0, steps),
                     fault_injector=monkey)
        marker = read_resume_marker(d)
        assert marker is not None and marker["step"] == tr.step == 4
        assert latest_valid_step(d) == 4  # final save is durable + valid
        shutdown.requested = False
        res = Trainer(cfg, _tcfg(steps), ckpt_dir=d, shutdown=shutdown)
        assert res.maybe_restore() and res.step == 4
        res.train(_step_batches(ds, caps, res.step, steps))
    assert res.step == steps
    for a, b in zip(jax.tree.leaves(ref.params), jax.tree.leaves(res.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def _chaos_run(ds, caps, cfg, d, *, steps=8, ckpt_every=2,
               chaos="nan@3,nan@4", max_attempts=6):
    """Launcher-style restart loop under a chaos schedule; returns
    (trainer, full metric history, stats aggregated across attempts —
    each attempt builds a fresh Trainer, as a relaunched process would)."""
    monkey = ChaosMonkey(ChaosSchedule.parse(chaos), ckpt_dir=d)
    history, attempts = [], 0
    stats = {"rollbacks": 0, "quarantined": set()}
    while True:
        attempts += 1
        assert attempts <= max_attempts
        tr = Trainer(cfg, _tcfg(steps, rollback_on_divergence=True,
                                divergence_nan_streak=2),
                     ckpt_dir=d, ckpt_every=ckpt_every)
        tr.maybe_restore()
        stream = monkey.wrap_batches(
            _step_batches(ds, caps, tr.step, steps, tag=True),
            start_step=tr.step)
        try:
            history.extend(tr.train(stream, fault_injector=monkey))
        except PreemptionError:
            raise
        except Exception as exc:  # injected crash: restart
            history.extend(getattr(exc, "partial_history", []))
            tr.close()
            continue
        finally:
            stats["rollbacks"] += tr.rollbacks
            stats["quarantined"] |= tr.quarantined
        if tr.step >= steps:
            return tr, history, stats


def test_nan_rollback_quarantines_and_descends(setup, tmp_path):
    ds, caps, cfg = setup
    tr, history, stats = _chaos_run(ds, caps, cfg, str(tmp_path))
    assert tr.step == 8
    assert stats["rollbacks"] == 1
    assert stats["quarantined"]  # the streak's batch indices are blacklisted
    finite = [h["loss"] for h in history if np.isfinite(h["loss"])]
    assert np.isfinite(history[-1]["loss"])
    assert finite[-1] < finite[0]  # still learning after the rollback
    # every surviving checkpoint passes verification (healthy-only saves)
    d = str(tmp_path)
    assert all(verify_checkpoint(_ckpt_path(d, s))
               for s in list_checkpoints(d))


def test_same_seed_and_schedule_identical_history(setup, tmp_path):
    ds, caps, cfg = setup
    _, h1, _ = _chaos_run(ds, caps, cfg, str(tmp_path / "run1"))
    _, h2, _ = _chaos_run(ds, caps, cfg, str(tmp_path / "run2"))
    assert len(h1) == len(h2)
    # bit-identical metric dicts, replayed faults & all (NaN == NaN here)
    np.testing.assert_equal(h1, h2)


def test_crash_recovery_bounded_rework(setup, tmp_path):
    ds, caps, cfg = setup
    tr, history, _ = _chaos_run(ds, caps, cfg, str(tmp_path),
                                chaos="crash@5", ckpt_every=2)
    assert tr.step == 8
    # rework = executed - final: crash at 5, restore at 4 -> exactly 1
    assert len(history) - tr.step <= 2


def test_tagged_batches_reach_trainer(setup):
    ds, caps, _ = setup
    batch = next(_step_batches(ds, caps, 0, 1, tag=True))
    assert isinstance(batch, TaggedBatch)
    assert len(np.asarray(batch.indices)) == BATCH
    # TaggedBatch is a pytree: chaos poisoning and device_put must recurse
    leaves = jax.tree.leaves(batch)
    assert len(leaves) > 1

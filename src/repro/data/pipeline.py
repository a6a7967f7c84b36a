"""Training-side batch iteration + asynchronous prefetch (paper C8).

All capacity/packing policy lives in ``repro.batching`` (bucketed capacity
ladders, padded packing, compile cache); this module is the glue between a
dataset, the samplers (paper C6) and that engine:

  - ``BatchIterator`` accepts either one fixed ``BatchCapacities`` or a
    ``CapacityLadder`` — with a ladder each global batch is packed into the
    smallest bucket that fits its largest shard, so typical batches stop
    paying the worst-case pad (the LoadBalanceSampler keeps shard totals
    tight, which is what makes small buckets hit often);
  - non-divisible global batches (``batch_size % num_devices != 0``) are
    handled by padding every shard to a fixed number of *crystal slots*,
    so per-device batches always stack to one shape.

Prefetch: a background thread builds + device_puts the next batch while the
current step runs (JAX dispatch is async) — the JAX analogue of the paper's
separate CUDA copy stream.  Worker exceptions are captured and re-raised in
the consumer, not swallowed.
"""
from __future__ import annotations

import dataclasses
import logging
import math
import queue
import threading
import time
from typing import Any, NamedTuple

import jax
import numpy as np

from repro.batching import (
    BatchCapacities,
    CapacityLadder,
    batch_crystals,
    capacity_for,
    ladder_for,
    stack_device_batches,
)
from repro.batching.balance import (
    StepPlan,
    crystal_slots_for,
    plan_microbatches,
    shard_cost_totals,
)
from repro.batching.cost import DEFAULT_COST_MODEL, CostModel
from repro.core.graph import CrystalGraphBatch
from repro.core.losses import global_denominators
from repro.runtime import spans
from repro.runtime.fault import TransientSampleError
from .sampler import CostBalanceSampler, DefaultSampler, LoadBalanceSampler
from .synthetic import SyntheticDataset

__all__ = [
    "BatchIterator", "BalancedBatchIterator", "Prefetcher", "TaggedBatch",
    "TransientSampleError", "build_device_batch", "stack_device_batches",
    "capacity_for", "ladder_for",
]

log = logging.getLogger("repro.data")


class TaggedBatch(NamedTuple):
    """A packed batch plus the dataset indices it was built from.

    The Trainer unwraps it before the jitted step and keeps the indices
    in a ring buffer, so a divergence rollback can quarantine the streak's
    source samples (DESIGN.md §8).  Being a NamedTuple it is a pytree —
    ``jax.device_put`` in the Prefetcher passes through it fine.
    """

    indices: np.ndarray
    batch: Any


def build_device_batch(
    ds: SyntheticDataset,
    indices: np.ndarray,
    caps: BatchCapacities,
    *,
    num_crystal_slots: int | None = None,
    validate: bool = True,
) -> CrystalGraphBatch:
    return batch_crystals(
        [ds.crystals[i] for i in indices],
        [ds.graphs[i] for i in indices],
        caps,
        num_crystal_slots=num_crystal_slots,
        validate=validate,
    )


class BatchIterator:
    """Epoch iterator producing stacked per-device padded batches."""

    def __init__(
        self,
        ds: SyntheticDataset,
        global_batch: int,
        num_devices: int,
        caps: BatchCapacities | CapacityLadder,
        *,
        load_balance: bool | str = True,
        seed: int = 0,
        stack: bool | None = None,
        drop_last: bool = True,
        validate_layout: bool = True,
        cost_model: CostModel | None = None,
        tag_indices: bool = False,
    ):
        if num_devices < 1:
            raise ValueError(f"num_devices must be >= 1, got {num_devices}")
        if global_batch < num_devices:
            raise ValueError(
                f"global_batch {global_batch} < num_devices {num_devices}"
            )
        self.ds = ds
        self.global_batch = global_batch
        self.num_devices = num_devices
        self.caps = caps
        self.drop_last = drop_last
        # quarantine (DESIGN.md §8): indices here are dropped from every
        # subsequent batch (the crystal-slot pad absorbs the shorter
        # shards); tag_indices wraps each yield in a TaggedBatch so the
        # Trainer can trace a divergence back to its source samples
        self.tag_indices = tag_indices
        self.quarantine: set[int] = set()
        # per-batch sorted-segment layout check (DESIGN.md §1); steady-state
        # epoch loops over a trusted dataset can turn it off — packing
        # establishes the invariant either way
        self.validate_layout = validate_layout
        # stacked (num_devices, ...) leaves for shard_map; plain batch else
        self.stack = (num_devices > 1) if stack is None else stack
        if load_balance == "cost":
            # LPT bin packing over a cost model (DESIGN.md §6): shards may
            # hold unequal sample counts, so the static crystal-slot pad
            # needs LPT's 2x headroom (crystal_slots_for) instead of
            # ceil(batch / devices)
            model = cost_model if cost_model is not None \
                else DEFAULT_COST_MODEL
            self.crystal_slots = crystal_slots_for(global_batch, num_devices)
            self.sampler = CostBalanceSampler(
                model.predict_dataset(ds), seed,
                max_items=self.crystal_slots)
        else:
            # every shard is padded to this many crystal slots so that
            # shards of unequal length (non-divisible global batch) stack
            self.crystal_slots = math.ceil(global_batch / num_devices)
            counts = ds.feature_counts()
            self.sampler = (
                LoadBalanceSampler(counts, seed)
                if load_balance
                else DefaultSampler(counts, seed)
            )

    def _caps_for(self, shards: list[np.ndarray]) -> BatchCapacities:
        """One capacity for all shards of this step (shapes must match)."""
        if isinstance(self.caps, BatchCapacities):
            return self.caps
        na = nb = ng = 0
        for s in shards:
            na = max(na, sum(self.ds.crystals[i].num_atoms for i in s))
            nb = max(nb, sum(self.ds.graphs[i].num_bonds for i in s))
            ng = max(ng, sum(self.ds.graphs[i].num_angles for i in s))
        return self.caps.bucket_for(na, nb, ng)

    def add_quarantine(self, indices) -> None:
        """Exclude dataset indices from all future batches (the Trainer's
        ``on_quarantine`` hook points here)."""
        self.quarantine.update(int(i) for i in np.asarray(indices).ravel())

    def _filter_quarantined(self, shards: list[np.ndarray]):
        """Drop quarantined indices; None if any shard would go empty
        (skip the step — shapes must stay stackable)."""
        if not self.quarantine:
            return shards
        q = np.fromiter(self.quarantine, dtype=np.int64)
        out = [s[~np.isin(s, q)] for s in shards]
        if any(len(s) == 0 for s in out):
            return None
        return out

    def __iter__(self):
        for _idx, shards in self.sampler.epoch(
            self.global_batch, self.num_devices, drop_last=self.drop_last
        ):
            shards = self._filter_quarantined(shards)
            if shards is None:
                continue
            caps = self._caps_for(shards)
            batches = [
                build_device_batch(
                    self.ds, s, caps, num_crystal_slots=self.crystal_slots,
                    validate=self.validate_layout,
                )
                for s in shards
            ]
            if self.stack:
                out = stack_device_batches(batches)
            else:
                assert len(batches) == 1
                out = batches[0]
            if self.tag_indices:
                yield TaggedBatch(np.concatenate(shards), out)
            else:
                yield out


class BalancedBatchIterator:
    """Epoch iterator producing :class:`StepPlan` s (DESIGN.md §6).

    One yielded plan = one optimizer step = ``num_micro`` microbatches,
    each LPT-packed across devices by predicted cost and packed into its
    OWN smallest-fitting capacity bucket.  The Trainer's accumulation
    path (``repro.train.trainer.make_chgnet_accum_step_fns``) sums the
    per-microbatch grads, whose global-denominator losses make the summed
    update exactly equal a single big-batch step.

    Compared to :class:`BatchIterator` this trades one big compiled step
    for ``num_micro`` smaller ones: the big-crystal microbatch pays the
    big bucket, the rest don't — padded-slot waste and the straggler gap
    both drop (``benchmarks/bench_scaling`` measures the latter).
    """

    def __init__(
        self,
        ds: SyntheticDataset,
        global_batch: int,
        num_devices: int,
        caps: BatchCapacities | CapacityLadder,
        *,
        num_micro: int = 1,
        cost_model: CostModel | None = None,
        seed: int = 0,
        stack: bool | None = None,
        drop_last: bool = True,
        validate_layout: bool = True,
    ):
        if num_devices < 1:
            raise ValueError(f"num_devices must be >= 1, got {num_devices}")
        if global_batch < num_devices:
            raise ValueError(
                f"global_batch {global_batch} < num_devices {num_devices}")
        self.ds = ds
        self.global_batch = global_batch
        self.num_devices = num_devices
        self.caps = caps
        self.num_micro = max(1, num_micro)
        self.cost_model = cost_model if cost_model is not None \
            else DEFAULT_COST_MODEL
        self.costs = self.cost_model.predict_dataset(ds)
        self.atoms = np.array([c.num_atoms for c in ds.crystals])
        self.rng = np.random.default_rng(seed)
        self.stack = (num_devices > 1) if stack is None else stack
        self.drop_last = drop_last
        self.validate_layout = validate_layout
        # static per-shard crystal-slot pad: fixed per (global_batch,
        # num_micro, num_devices), so the jit cache sees ONE crystal-axis
        # shape per bucket regardless of how LPT splits a given step
        self.crystal_slots = crystal_slots_for(
            global_batch, num_devices, self.num_micro)
        self.quarantine: set[int] = set()

    def add_quarantine(self, indices) -> None:
        """Exclude dataset indices from all future StepPlans."""
        self.quarantine.update(int(i) for i in np.asarray(indices).ravel())

    def _caps_for(self, shards: list[np.ndarray]) -> BatchCapacities:
        """Smallest bucket fitting this microbatch's largest shard."""
        if isinstance(self.caps, BatchCapacities):
            return self.caps
        na = nb = ng = 0
        for s in shards:
            na = max(na, sum(self.ds.crystals[i].num_atoms for i in s))
            nb = max(nb, sum(self.ds.graphs[i].num_bonds for i in s))
            ng = max(ng, sum(self.ds.graphs[i].num_angles for i in s))
        return self.caps.bucket_for(na, nb, ng)

    def update_cost_model(self, model: CostModel) -> None:
        """Swap in a refit cost model (live refits, DESIGN.md §6).

        Called between steps by ``Trainer`` (via ``on_cost_model``) after
        it refits the model from measured per-microbatch wall times; every
        subsequent ``plan_step`` LPT-packs with the new coefficients.
        Cheap and host-side only (one predict over the dataset).
        """
        self.cost_model = model
        self.costs = model.predict_dataset(self.ds)

    def plan_step(self, idx: np.ndarray) -> StepPlan:
        """Pack one global batch's indices into a balanced StepPlan."""
        idx = np.asarray(idx)
        plan = plan_microbatches(
            self.costs[idx], self.num_devices, self.num_micro,
            max_items=self.crystal_slots)
        micro_batches = []
        shard_costs = np.zeros((len(plan), self.num_devices), np.float64)
        micro_sizes = np.zeros((len(plan), 3), np.float64)
        for m, shards_pos in enumerate(plan):
            shards = [idx[pos] for pos in shards_pos]
            caps = self._caps_for(shards)
            batches = [
                build_device_batch(
                    self.ds, s, caps,
                    num_crystal_slots=self.crystal_slots,
                    validate=self.validate_layout,
                )
                for s in shards
            ]
            shard_costs[m] = shard_cost_totals(self.costs, shards)
            # real feature totals, host-side (no device syncs): the live
            # cost-model refit pairs these with measured micro wall times
            flat = np.concatenate(shards)
            micro_sizes[m] = (
                sum(self.ds.crystals[i].num_atoms for i in flat),
                sum(self.ds.graphs[i].num_bonds for i in flat),
                sum(self.ds.graphs[i].num_angles for i in flat),
            )
            if self.stack:
                micro_batches.append(stack_device_batches(batches))
            else:
                assert len(batches) == 1
                micro_batches.append(batches[0])
        denoms = global_denominators(
            len(idx), int(self.atoms[idx].sum()))
        return StepPlan(micro=micro_batches, denoms=denoms,
                        shard_costs=shard_costs, num_real=len(idx),
                        micro_sizes=micro_sizes)

    def __iter__(self):
        n = len(self.ds)
        perm = self.rng.permutation(n)
        from .sampler import _epoch_slices
        for s, e in _epoch_slices(n, self.global_batch, self.num_devices,
                                  self.drop_last):
            idx = perm[s:e]
            if self.quarantine:
                q = np.fromiter(self.quarantine, dtype=np.int64)
                idx = idx[~np.isin(idx, q)]
                if len(idx) < self.num_devices:
                    continue  # too few survivors to fill every shard
            yield self.plan_step(idx)


def place(item, device):
    """``jax.device_put`` one stream item onto ``device`` (a device or a
    sharding): a plain batch, a :class:`TaggedBatch`'s batch, or every
    microbatch of a :class:`StepPlan` (its host-side plan metadata stays
    on the host).  Stacked ``(n_dev, ...)`` batches placed with a
    ``NamedSharding`` over the data axis land one shard per device, so
    the step never stages the whole batch on device 0."""
    if isinstance(item, TaggedBatch):
        return item._replace(batch=place(item.batch, device))
    if isinstance(item, StepPlan):
        return dataclasses.replace(
            item, micro=[jax.device_put(m, device) for m in item.micro])
    return jax.device_put(item, device)


class Prefetcher:
    """Background-thread prefetch of up to ``depth`` device-put batches.

    ``device`` (a device or a sharding, see :func:`place`) is where each
    item lands; ``None`` leaves items on the host.

    A worker-thread exception is captured and re-raised in the consumer at
    the point of failure — a bad batch must fail the epoch loudly, not
    silently truncate it.  Two exceptions (DESIGN.md §8):

      - :class:`~repro.runtime.fault.TransientSampleError` from the source
        is retried with bounded exponential backoff: the offending index
        is logged + recorded in ``self.quarantined`` and the stream moves
        on (the source must be resumable across the raise — e.g. the
        chaos wrapper; a plain generator dies on its first raise).  Only
        ``max_retries`` CONSECUTIVE transient failures escalate to the
        consumer.
      - Early consumer exit: breaking out of the ``for`` loop (or any
        ``close()``) unblocks a worker stuck on the full queue and joins
        it with a timeout — the old implementation leaked a thread
        blocked on ``q.put`` forever.
    """

    _STOP = object()

    def __init__(self, iterator, depth: int = 2, device=None, *,
                 max_retries: int = 3, backoff: float = 0.02):
        self.q: queue.Queue = queue.Queue(maxsize=depth)
        self.device = device
        self._error: BaseException | None = None
        self.max_retries = max_retries
        self.backoff = backoff
        self.quarantined: list[int | None] = []
        self._closed = threading.Event()
        self._source = iter(iterator)
        self.thread = threading.Thread(target=self._worker, daemon=True)
        self.thread.start()

    def _put(self, item) -> bool:
        """put that gives up when the consumer closed us."""
        while not self._closed.is_set():
            try:
                self.q.put(item, timeout=0.05)
                return True
            except queue.Full:
                continue
        return False

    def _worker(self):
        retries = 0
        try:
            while not self._closed.is_set():
                try:
                    with spans.span("data.produce"):
                        item = next(self._source)
                        if self.device is not None:
                            item = place(item, self.device)
                except StopIteration:
                    break
                except TransientSampleError as exc:
                    retries += 1
                    self.quarantined.append(exc.index)
                    log.warning(
                        "prefetch: transient sample failure (index=%s), "
                        "quarantined; retry %d/%d", exc.index, retries,
                        self.max_retries)
                    if retries > self.max_retries:
                        self._error = exc
                        break
                    time.sleep(self.backoff * (2 ** (retries - 1)))
                    continue
                retries = 0
                if not self._put(item):
                    return  # closed mid-put: consumer is gone
        except BaseException as e:  # re-raised in the consumer
            self._error = e
        self._put(self._STOP)

    def close(self, timeout: float = 5.0) -> None:
        """Stop the worker: signal, drain the queue (unblocking a full
        ``put``), join with ``timeout``.  Idempotent; called automatically
        when the consumer's iteration ends for ANY reason."""
        self._closed.set()
        try:
            while True:
                self.q.get_nowait()
        except queue.Empty:
            pass
        self.thread.join(timeout)

    def __iter__(self):
        try:
            while True:
                try:
                    with spans.span("data.wait"):
                        item = self.q.get(timeout=0.1)
                except queue.Empty:
                    if self._closed.is_set() or not self.thread.is_alive():
                        break  # worker gone without a sentinel
                    continue
                if item is self._STOP:
                    break
                yield item
            if self._error is not None:
                raise self._error
        finally:
            self.close()

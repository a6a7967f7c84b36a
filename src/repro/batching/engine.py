"""The batching engine: bucket selection + a jit compile cache.

One ``BatchingEngine`` is shared by training and serving.  It owns

  - a ``CapacityLadder`` (bucket selection, never truncating), and
  - a ``CompileCache`` keyed on ``(name, bucket, batch_size, config)`` so
    each padded shape/config combination is traced exactly once per
    process, even across Trainer restarts or many serve replica groups.

``jax.jit`` already caches per *abstract shape*, but a fresh ``jit``
wrapper (e.g. a new Trainer after a fault restart, or an ad-hoc lambda per
call site) starts with an empty cache; routing construction through
``CompileCache`` makes the reuse explicit and measurable (hits/misses).
"""
from __future__ import annotations

import threading
from typing import Callable

from repro.core.neighbors import Crystal, GraphIndices

from .capacity import BatchCapacities, CapacityLadder
from .pack import batch_crystals


class CompileCache:
    """Process-wide memo of built (usually jitted) step functions."""

    def __init__(self):
        self._fns: dict = {}
        self._lock = threading.Lock()
        self.hits = 0
        self.misses = 0

    def get(self, key, build: Callable[[], Callable]) -> Callable:
        with self._lock:
            fn = self._fns.get(key)
            if fn is not None:
                self.hits += 1
                return fn
            self.misses += 1
        # build outside the lock (tracing can be slow); last writer wins
        fn = build()
        with self._lock:
            return self._fns.setdefault(key, fn)

    def __len__(self) -> int:
        return len(self._fns)

    def clear(self) -> None:
        with self._lock:
            self._fns.clear()
            self.hits = 0
            self.misses = 0


_GLOBAL_CACHE = CompileCache()


def global_compile_cache() -> CompileCache:
    """The default process-wide compile cache."""
    return _GLOBAL_CACHE


class BatchingEngine:
    """Packs crystal lists into bucketed padded batches + caches step fns.

    Tracks padding-waste statistics so the padding-efficiency claim
    (bucketing beats one worst-case capacity) is directly measurable:
    ``packed`` and ``capacity`` count the real and the padded atom, bond
    and angle rows of every batch packed, from the graphs on the host
    before any device transfer.
    """

    def __init__(self, ladder: CapacityLadder,
                 cache: CompileCache | None = None,
                 *, validate_layout: bool = True):
        self.ladder = ladder
        self.cache = cache if cache is not None else global_compile_cache()
        # sorted-segment layout check on every packed batch (DESIGN.md §1);
        # a few O(E) numpy passes — serving loops that trust their graph
        # producers can turn it off
        self.validate_layout = validate_layout
        self.batches_packed = 0
        self.packed = {"atoms": 0, "bonds": 0, "angles": 0}
        self.capacity = {"atoms": 0, "bonds": 0, "angles": 0}
        self._waste_sum = 0.0

    # -- bucket selection ---------------------------------------------------
    def select(self, crystals: list[Crystal],
               graphs: list[GraphIndices]) -> BatchCapacities:
        """Smallest ladder bucket that fits the batch totals."""
        return self.ladder.bucket_for(
            sum(c.num_atoms for c in crystals),
            sum(g.num_bonds for g in graphs),
            sum(g.num_angles for g in graphs),
        )

    # -- packing ------------------------------------------------------------
    def pack(
        self,
        crystals: list[Crystal],
        graphs: list[GraphIndices],
        *,
        caps: BatchCapacities | None = None,
        num_crystal_slots: int | None = None,
    ):
        """Pack into the smallest fitting bucket; returns (batch, bucket)."""
        caps = caps if caps is not None else self.select(crystals, graphs)
        batch = batch_crystals(
            crystals, graphs, caps, num_crystal_slots=num_crystal_slots,
            validate=self.validate_layout,
        )
        real = {"atoms": sum(c.num_atoms for c in crystals),
                "bonds": sum(g.num_bonds for g in graphs),
                "angles": sum(g.num_angles for g in graphs)}
        cap = {"atoms": caps.atoms, "bonds": caps.bonds,
               "angles": caps.angles}
        for k in real:
            self.packed[k] += real[k]
            self.capacity[k] += cap[k]
        self.batches_packed += 1
        total = sum(cap.values())
        self._waste_sum += 1.0 - sum(real.values()) / total if total else 0.0
        return batch, caps

    # -- compiled step functions -------------------------------------------
    def compiled(self, name: str, caps: BatchCapacities, batch_size: int,
                 config_key, build: Callable[[], Callable]) -> Callable:
        """Memoized step function for ``(name, bucket, batch_size, config)``."""
        return self.cache.get((name, caps, batch_size, config_key), build)

    # -- stats --------------------------------------------------------------
    @property
    def mean_padding_waste(self) -> float:
        return self._waste_sum / self.batches_packed if self.batches_packed else 0.0

    def stats(self) -> dict:
        return {
            "batches_packed": self.batches_packed,
            "mean_padding_waste": self.mean_padding_waste,
            "packed": dict(self.packed),
            "capacity": dict(self.capacity),
            "compile_cache_entries": len(self.cache),
            "compile_cache_hits": self.cache.hits,
            "compile_cache_misses": self.cache.misses,
        }

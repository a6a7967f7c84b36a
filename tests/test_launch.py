"""Entry-point plumbing: the launcher's in-process ``main(argv)``, the
persistent compile-cache helper, and sharded batch placement."""
import math
import os

import jax
import numpy as np
import pytest
from jax.experimental.compilation_cache import compilation_cache
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.batching import capacity_for
from repro.data import (
    BalancedBatchIterator, SyntheticConfig, TaggedBatch, make_dataset,
)
from repro.data.pipeline import place
from repro.launch import compile_cache

REPO = os.path.normpath(os.path.join(os.path.dirname(__file__), ".."))


@pytest.fixture
def cache_config():
    """Restore JAX's compile-cache settings after a test turned them on,
    so later tests in this worker compile without a persistent cache."""
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs")
    saved = {k: getattr(jax.config, k) for k in keys}
    yield
    for k, v in saved.items():
        jax.config.update(k, v)
    compilation_cache.reset_cache()


def test_compile_cache_uses_env_dir(monkeypatch, tmp_path, cache_config):
    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == str(tmp_path)


def test_compile_cache_default_is_ignored_path_in_checkout(monkeypatch,
                                                           cache_config):
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    path = compile_cache.enable_compile_cache()
    assert path == os.path.join(REPO, ".jax_cache")
    with open(os.path.join(REPO, ".gitignore")) as fh:
        assert ".jax_cache/" in fh.read().split()


def test_launcher_main_returns_history(monkeypatch, tmp_path, cache_config):
    from repro.launch.train import main

    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
    # one device whatever this worker's forced host device count: the
    # single-device step, as on one chip
    monkeypatch.setattr(jax, "device_count", lambda: 1)
    hist = main(["--arch", "chgnet", "--steps", "2", "--batch", "4",
                 "--crystals", "8", "--buckets", "1"])
    assert len(hist) == 2
    for h in hist:
        assert math.isfinite(h["loss"])
        assert h["step_s"] > 0


def test_place_puts_every_microbatch_on_the_sharding():
    n = min(jax.device_count(), 2)
    mesh = Mesh(np.array(jax.devices()[:n]), ("data",))
    ds = make_dataset(SyntheticConfig(num_crystals=4 * n, max_atoms=12,
                                      seed=0))
    caps = capacity_for(ds, 4)
    plan = BalancedBatchIterator(ds, 4 * n, n, caps, num_micro=2,
                                 stack=True).plan_step(np.arange(4 * n))
    sharding = NamedSharding(mesh, P("data"))
    placed = place(TaggedBatch(np.arange(4 * n), plan), sharding)
    assert placed.batch.denoms is plan.denoms  # host metadata stays put
    for micro in placed.batch.micro:
        for leaf in jax.tree.leaves(micro):
            assert leaf.sharding == sharding

"""Collective helpers, HLO parsing, input_specs plumbing, hypothesis props."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis")  # optional dep: skip, don't die, on bare envs
from hypothesis import given, settings, strategies as st

from repro.launch.dryrun import collective_stats, _shape_bytes


def test_shape_bytes_parsing():
    assert _shape_bytes("f32[4,8]") == 128
    assert _shape_bytes("bf16[2,2]{1,0}") == 8
    assert _shape_bytes("(f32[4], bf16[8])") == 32
    assert _shape_bytes("pred[]") == 1  # scalar => product of no dims = 1


def test_collective_stats_counts_and_factors():
    hlo = """
  %ag = f32[16,128]{1,0} all-gather(f32[4,128]{1,0} %x), replica_groups={{0,1,2,3}}, dimensions={0}
  %ar = (bf16[64]{0}) all-reduce(bf16[64]{0} %y), replica_groups={{0,1}}, to_apply=%add
  %cp = f32[8]{0} collective-permute(f32[8]{0} %z), source_target_pairs={{0,1}}
  %other = f32[4]{0} add(f32[4]{0} %a, f32[4]{0} %b)
"""
    out = collective_stats(hlo)
    assert out["count"] == 3
    assert set(out["by_op"]) == {"all-gather", "all-reduce",
                                 "collective-permute"}
    # all-gather: result 16*128*4 bytes * (4-1)/4
    assert out["by_op"]["all-gather"]["bytes"] == pytest.approx(
        16 * 128 * 4 * 0.75)
    # all-reduce: 2*(g-1)/g with g=2 -> factor 1.0
    assert out["by_op"]["all-reduce"]["bytes"] == pytest.approx(64 * 2 * 1.0)


def test_input_specs_all_cells():
    from repro.configs import ARCH_IDS, get_config
    from repro.configs.shapes import SHAPES, cell_status, input_specs

    sizes = {"data": 16, "model": 16}
    n_ok = n_skip = 0
    for arch in ARCH_IDS:
        cfg = get_config(arch)
        for shape in SHAPES.values():
            if cell_status(cfg, shape) != "ok":
                n_skip += 1
                continue
            io = input_specs(cfg, shape, multi_pod=False, mesh_sizes=sizes)
            assert len(io["args"]) == len(io["specs"])
            # every arg is a struct tree (no concrete arrays)
            for a in jax.tree.leaves(io["args"]):
                assert isinstance(a, jax.ShapeDtypeStruct)
            n_ok += 1
    assert n_ok == 32 and n_skip == 8  # 40 cells: 32 runnable + 8 skips


def test_long_context_skip_reasons():
    from repro.configs import get_config
    from repro.configs.shapes import SHAPES, cell_status

    assert cell_status(get_config("llama3-8b"), SHAPES["long_500k"]).startswith("skip")
    assert cell_status(get_config("rwkv6-3b"), SHAPES["long_500k"]) == "ok"
    assert cell_status(get_config("zamba2-1.2b"), SHAPES["long_500k"]) == "ok"


def test_bucketed_psum_single_device_identity():
    from jax.sharding import PartitionSpec as P

    from repro.distributed import bucketed_psum, compressed_psum

    mesh = jax.make_mesh((1,), ("data",),
                         axis_types=(jax.sharding.AxisType.Auto,))
    tree = {"a": jnp.arange(4.0), "b": jnp.ones((3, 3))}

    out = jax.shard_map(lambda t: bucketed_psum(t, "data"), mesh=mesh,
                        in_specs=(P(),), out_specs=P(),
                        check_vma=False)(tree)
    np.testing.assert_allclose(np.asarray(out["a"]), np.asarray(tree["a"]))

    out2 = jax.shard_map(lambda t: compressed_psum(t, "data"), mesh=mesh,
                         in_specs=(P(),), out_specs=P(),
                         check_vma=False)(tree)
    # bf16 rounding only
    np.testing.assert_allclose(np.asarray(out2["a"]), np.asarray(tree["a"]),
                               atol=2e-2)


@given(st.integers(1, 4096), st.integers(1, 64))
@settings(max_examples=50, deadline=None)
def test_default_accum_divides_batch(batch, dp):
    from repro.configs.shapes import Shape
    from repro.launch.steps import default_accum_steps
    from repro.models.config import LMConfig

    cfg = LMConfig(name="x", family="dense")
    shape = Shape("t", "train", 4096, batch)
    a = default_accum_steps(cfg, shape, dp)
    per_dev = max(1, batch // dp)
    assert 1 <= a <= per_dev
    assert per_dev % a == 0


@given(st.floats(-10, 10, width=32), st.floats(np.float32(0.01), np.float32(1.0), width=32))
@settings(max_examples=100, deadline=None)
def test_huber_properties(x, delta):
    from repro.core.losses import huber

    h = float(huber(jnp.asarray(x), delta))
    assert h >= 0
    # upper-bounded by both branches
    assert h <= 0.5 * x * x + 1e-6
    assert h <= delta * abs(x) + 1e-6


def test_cast_floats_preserves_ints():
    from repro.models.layers import cast_floats

    tree = {"w": jnp.ones((2,), jnp.float32), "i": jnp.ones((2,), jnp.int32)}
    out = cast_floats(tree, "bfloat16")
    assert out["w"].dtype == jnp.bfloat16
    assert out["i"].dtype == jnp.int32

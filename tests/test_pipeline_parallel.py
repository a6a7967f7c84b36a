"""GPipe pipeline parallelism: 4-stage device test (subprocess) + helpers."""
import json
import os
import subprocess
import sys
import textwrap

import pytest

from repro.distributed.pipeline import bubble_fraction

REPO = os.path.normpath(os.path.join(os.path.dirname(__file__), ".."))

_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import json
    import jax, jax.numpy as jnp
    import numpy as np
    from jax.sharding import PartitionSpec as P
    from repro.distributed.pipeline import gpipe_apply, split_stages

    L, D, M, MB = 8, 16, 6, 4   # layers, width, microbatches, microbatch sz
    S = 4
    rng = np.random.default_rng(0)
    ws = jnp.asarray(rng.normal(0, 0.3, (L, D, D)), jnp.float32)
    x = jnp.asarray(rng.normal(0, 1, (M, MB, D)), jnp.float32)

    def layer(w, h):
        return jnp.tanh(h @ w)

    # sequential reference
    def seq_forward(ws, x):
        h = x
        for i in range(L):
            h = layer(ws[i], h)
        return h

    ref = jax.vmap(lambda xm: seq_forward(ws, xm))(x)

    # pipelined
    mesh = jax.make_mesh((S,), ("pipe",),
                         axis_types=(jax.sharding.AxisType.Auto,))
    staged = split_stages(ws, S)

    def stage_fn(stage_ws, h):
        def body(h, w):
            return layer(w, h), None
        h, _ = jax.lax.scan(body, h, stage_ws)
        return h

    def pipe(staged, x):
        return gpipe_apply(staged, x, stage_fn, axis="pipe")

    piped = jax.shard_map(pipe, mesh=mesh, in_specs=(P("pipe"), P()),
                          out_specs=P(), check_vma=False)(staged, x)
    fwd_err = float(jnp.abs(piped - ref).max())

    # gradients through the pipeline == sequential gradients
    def loss_pipe(staged):
        return jnp.sum(jax.shard_map(
            pipe, mesh=mesh, in_specs=(P("pipe"), P()), out_specs=P(),
            check_vma=False)(staged, x) ** 2)

    def loss_seq(ws):
        return jnp.sum(jax.vmap(lambda xm: seq_forward(ws, xm))(x) ** 2)

    g_pipe = jax.grad(loss_pipe)(staged)
    g_seq = jax.grad(loss_seq)(ws).reshape(S, L // S, D, D)
    g_err = float(jnp.abs(g_pipe - g_seq).max())
    print(json.dumps({"fwd_err": fwd_err, "g_err": g_err}))
""")


def test_gpipe_matches_sequential_4_stages():
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
    out = subprocess.run([sys.executable, "-c", _SCRIPT],
                         capture_output=True, text=True, env=env,
                         timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    rec = json.loads(out.stdout.strip().splitlines()[-1])
    assert rec["fwd_err"] < 1e-5, rec
    assert rec["g_err"] < 1e-4, rec


def test_bubble_fraction():
    assert bubble_fraction(4, 6) == pytest.approx(3 / 9)
    assert bubble_fraction(1, 8) == 0.0
    # more microbatches -> smaller bubble
    assert bubble_fraction(4, 32) < bubble_fraction(4, 8)


def test_split_stages_shapes():
    import jax.numpy as jnp

    from repro.distributed.pipeline import split_stages

    tree = {"w": jnp.zeros((8, 3, 3)), "b": jnp.zeros((8, 3))}
    out = split_stages(tree, 4)
    assert out["w"].shape == (4, 2, 3, 3)
    assert out["b"].shape == (4, 2, 3)

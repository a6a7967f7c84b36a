"""The benchmark's weights: made on the device from ``--seed`` in one call.

The tree has the program's parameter layout (the interface both the
program and the plain reference read): GatedMLP weights stored as one
``[Wc | Wg]`` matrix with ``[core | gate]`` biases and LayerNorm affine
terms, and the three bond embeddings as one ``(num_rbf, 3 dim)`` linear.
Values are Glorot-normal weights, zero biases, unit LayerNorm scales, the
radial frequencies n pi, and a stress scale of 0.1.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

MAX_Z = 95  # element rows of the atom embedding


def seed_key(seed: int) -> np.ndarray:
    """A 32-bit PRNG seed from any whole ``--seed`` (larger than int32
    included), so every seed maps to its own key."""
    return np.random.SeedSequence(int(seed)).generate_state(1)[0]


def _glorot(key, d_in, d_out):
    return jax.random.normal(key, (d_in, d_out), jnp.float32) \
        * math.sqrt(2.0 / (d_in + d_out))


def _linear(key, d_in, d_out):
    return {"w": _glorot(key, d_in, d_out), "b": jnp.zeros((d_out,))}


def _gated(key, d_in, d_out):
    kc, kg = jax.random.split(key)
    return {"w": jnp.concatenate([_glorot(kc, d_in, d_out),
                                  _glorot(kg, d_in, d_out)], axis=1),
            "b": jnp.zeros((2 * d_out,)),
            "ln_scale": jnp.ones((2 * d_out,)),
            "ln_bias": jnp.zeros((2 * d_out,))}


def _mlp(key, dims):
    ks = jax.random.split(key, len(dims) - 1)
    return [_linear(k, a, b) for k, a, b in zip(ks, dims[:-1], dims[1:])]


def _block(key, dim):
    ks = jax.random.split(key, 5)
    return {"atom_mlp": _gated(ks[0], 3 * dim, dim),
            "atom_out": _linear(ks[1], dim, dim),
            "bond_mlp": _gated(ks[2], 4 * dim, dim),
            "bond_out": _linear(ks[3], dim, dim),
            "angle_mlp": _gated(ks[4], 4 * dim, dim)}


@partial(jax.jit, static_argnums=(1, 2, 3, 4, 5))
def _make(seed, dim, num_rbf, num_fourier, num_blocks, direct):
    ks = jax.random.split(jax.random.PRNGKey(seed), 8 + num_blocks)
    params = {
        "atom_embed": jax.random.normal(ks[0], (MAX_Z, dim)) * 0.02,
        "bond_embed": _linear(ks[1], num_rbf, 3 * dim),
        "angle_embed": _linear(ks[2], num_fourier, dim),
        "rbf_freqs": jnp.arange(1, num_rbf + 1, dtype=jnp.float32) * math.pi,
        "blocks": [_block(ks[3 + i], dim) for i in range(num_blocks)],
        "final_block": _block(ks[3 + num_blocks], dim),
        "energy_head": {"mlp": _mlp(ks[4 + num_blocks], (dim, dim, dim, 1))},
        "magmom_head": {"mlp": _mlp(ks[5 + num_blocks], (dim, dim, 1))},
    }
    if direct:
        params["force_head"] = {"mlp": _mlp(ks[6 + num_blocks],
                                            (dim, dim, 1))}
        params["stress_head"] = {"mlp": _mlp(ks[7 + num_blocks],
                                             (dim, dim, 9)),
                                 "scale": jnp.asarray(0.1, jnp.float32)}
    return params


def make_weights(config: dict, seed: int, device=None):
    """Float32 weights for ``config`` (a ``configs/*.json`` dict) from
    ``seed``, made on ``device`` (default: JAX's first device)."""
    with jax.default_device(device or jax.devices()[0]):
        return _make(seed_key(seed), config["dim"], config["num_rbf"],
                     config["num_fourier"], config["num_blocks"],
                     config["readout"] == "direct")

"""Fused-Fourier Pallas kernel (paper 'Fused-Fourier', C4).

Computes the angle basis [1/sqrt(2), cos(n*t), sin(n*t)] / sqrt(pi) for
n = 1..L in one VMEM pass using a lane-index select instead of a concat:
lane 0 is the DC term, lanes 1..L are cosines, lanes L+1..2L are sines.
Lanes >= num_basis (alignment padding) carry zeros and are sliced off by
the ops.py wrapper.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(theta_ref, out_ref, *, harmonics: int, num_basis: int):
    t = theta_ref[...]  # (bm, 1)
    bm, k = out_ref.shape
    lane = jax.lax.broadcasted_iota(jnp.int32, (bm, k), 1)
    # harmonic index per lane: cos lanes use n = lane, sin lanes n = lane - L
    n_cos = lane.astype(t.dtype)
    n_sin = (lane - harmonics).astype(t.dtype)
    is_dc = lane == 0
    is_cos = (lane >= 1) & (lane <= harmonics)
    is_sin = (lane > harmonics) & (lane < num_basis)
    ang_cos = t * n_cos
    ang_sin = t * n_sin
    inv_sqrt_pi = 1.0 / jnp.sqrt(jnp.pi)
    val = jnp.where(
        is_dc,
        1.0 / jnp.sqrt(2.0),
        jnp.where(is_cos, jnp.cos(ang_cos), jnp.sin(ang_sin)),
    )
    out_ref[...] = jnp.where(is_dc | is_cos | is_sin, val * inv_sqrt_pi, 0.0)


def fused_fourier_pallas(
    theta: jnp.ndarray,  # (N,) f32, N % block_m == 0
    num_basis: int,
    *,
    k_pad: int = 128,
    block_m: int = 512,
    interpret: bool,
) -> jnp.ndarray:
    n = theta.shape[0]
    assert n % block_m == 0, (n, block_m)
    assert num_basis % 2 == 1 and num_basis <= k_pad
    harmonics = (num_basis - 1) // 2
    grid = (n // block_m,)
    return pl.pallas_call(
        functools.partial(_kernel, harmonics=harmonics, num_basis=num_basis),
        grid=grid,
        in_specs=[pl.BlockSpec((block_m, 1), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((block_m, k_pad), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, k_pad), theta.dtype),
        interpret=interpret,
    )(theta[:, None])

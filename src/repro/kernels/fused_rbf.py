"""Fused-sRBF Pallas kernel (paper 'Fused-sRBF', C4 + C5).

One VMEM-resident kernel computes, per bond distance:
    xi = r / r_cut
    u(xi)        -- factored Horner envelope (Eq. 13, C5)
    sin(f_n xi)  -- trainable-frequency Bessel numerators
    out[n] = sqrt(2/rc) * sin(f_n xi) / r * u(xi)

The reference implementation materializes 4+ HBM-round-trip intermediates
(xi, powers, envelope, phases); here everything stays in VMEM. Distances
are carried as an (N, 1) column so the block layout is TPU-native
(8x128-aligned); the basis axis is padded to a multiple of 128 lanes by the
ops.py wrapper.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(dist_ref, freq_ref, out_ref, *, r_cut: float, p: int):
    r = dist_ref[...]  # (bm, 1)
    xi = r / r_cut
    # factored envelope (Eq. 13 corrected), Horner: one pow, two fma
    inner = (p + 1.0) * (p + 2.0) + xi * (
        -2.0 * p * (p + 2.0) + xi * (p * (p + 1.0)))
    u = 1.0 - 0.5 * xi**p * inner
    r_safe = jnp.where(r > 1e-8, r, 1.0)
    phases = xi * freq_ref[...]  # (bm, 1) * (1, K) -> (bm, K)
    out_ref[...] = (jnp.sqrt(2.0 / r_cut) * jnp.sin(phases) / r_safe) * u


def fused_rbf_pallas(
    dist: jnp.ndarray,   # (N,) f32, N % block_m == 0
    freqs: jnp.ndarray,  # (K,) f32, K % 128 == 0 (padded by wrapper)
    r_cut: float,
    p: int = 8,
    *,
    block_m: int = 512,
    interpret: bool,
) -> jnp.ndarray:
    n = dist.shape[0]
    k = freqs.shape[0]
    assert n % block_m == 0, (n, block_m)
    grid = (n // block_m,)
    return pl.pallas_call(
        functools.partial(_kernel, r_cut=r_cut, p=p),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, 1), lambda i: (i, 0)),
            pl.BlockSpec((1, k), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((block_m, k), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, k), dist.dtype),
        interpret=interpret,
    )(dist[:, None], freqs[None, :])

"""Fused GatedMLP Pallas kernel (paper Fig. 3, C4).

Implements phi(x) = silu(LN(x@Wc+bc)) * sigmoid(LN(x@Wg+bg)) with:
  - ONE packed GEMM against [Wc ‖ Wg] (Fig. 3a) hitting the MXU once,
  - shared epilogue in VMEM: both LayerNorms + gating (Fig. 3b),
  - silu(x) = x * sigmoid(x): a single kind of sigmoid evaluation.

Layout: CHGNet dims are d_in ∈ {192, 256}, d_out = 64 — the packed output
is exactly 128 lanes (core ‖ gate), the native TPU lane width. Rows are
tiled by ``block_m``; weights are small enough to stay fully VMEM-resident
(256 x 128 x 4 B = 128 KiB).

Precision (DESIGN.md §4): operands may be bf16 (halving the VMEM tiles) —
the GEMM accumulates f32 on the MXU (``preferred_element_type``), the
LayerNorm statistics and the gating epilogue are evaluated in f32, and
only the final write casts back to the operand dtype.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _ln(x, scale, bias, eps=1e-5):
    # f32 statistics (x arrives f32 from the accumulating GEMM)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * scale + bias


def _kernel(x_ref, w_ref, b_ref, lns_ref, lno_ref, out_ref, *, d_out: int):
    x = x_ref[...]                       # (bm, d_in), f32 or bf16
    w = w_ref[...]                       # (d_in, 2*d_out), same dtype
    # bf16 x bf16 -> f32 on the MXU: in-register accumulation stays f32
    y = jnp.dot(x, w, preferred_element_type=jnp.float32) \
        + b_ref[...].astype(jnp.float32)
    core = y[:, :d_out]
    gate = y[:, d_out:]
    core = _ln(core, lns_ref[0, :d_out].astype(jnp.float32),
               lno_ref[0, :d_out].astype(jnp.float32))
    gate = _ln(gate, lns_ref[0, d_out:].astype(jnp.float32),
               lno_ref[0, d_out:].astype(jnp.float32))
    sig_core = jax.nn.sigmoid(core)
    sig_gate = jax.nn.sigmoid(gate)
    # silu(core) = core * sigmoid(core): sigmoid reuse (Fig. 3b dashed line)
    out_ref[...] = ((core * sig_core) * sig_gate).astype(out_ref.dtype)


def fused_gated_mlp_pallas(
    x: jnp.ndarray,        # (M, d_in), M % block_m == 0
    w_packed: jnp.ndarray,  # (d_in, 2*d_out) = [Wc ‖ Wg]
    b_packed: jnp.ndarray,  # (2*d_out,)
    ln_scale: jnp.ndarray,  # (2*d_out,) = [core_scale ‖ gate_scale]
    ln_bias: jnp.ndarray,   # (2*d_out,)
    *,
    block_m: int = 256,
    interpret: bool,
) -> jnp.ndarray:
    m, d_in = x.shape
    two_d = w_packed.shape[1]
    d_out = two_d // 2
    assert m % block_m == 0, (m, block_m)
    grid = (m // block_m,)
    return pl.pallas_call(
        functools.partial(_kernel, d_out=d_out),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, d_in), lambda i: (i, 0)),
            pl.BlockSpec((d_in, two_d), lambda i: (0, 0)),
            pl.BlockSpec((1, two_d), lambda i: (0, 0)),
            pl.BlockSpec((1, two_d), lambda i: (0, 0)),
            pl.BlockSpec((1, two_d), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((block_m, d_out), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((m, d_out), x.dtype),
        interpret=interpret,
    )(x, w_packed, b_packed[None, :], ln_scale[None, :], ln_bias[None, :])

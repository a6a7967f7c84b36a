"""Fused SwiGLU/GeGLU MLP Pallas kernel — paper C4 generalized to the LM
substrate (DESIGN.md §4 "transfers directly").

Computes the ENTIRE gated MLP in one kernel:
    y = (act(x @ W_gate) * (x @ W_up)) @ W_down
act = silu (SwiGLU, llama-family) or gelu (GeGLU, gemma).

Grid is (M / bm, F / bf): the ff dimension is the reduction axis of the
second GEMM, so the output block index map ignores j and the kernel
accumulates into out_ref across j steps (initialized at j == 0). The
gate/up activations for the (i, j) tile never leave VMEM — this removes
the (M x F) activation HBM round-trip that an unfused MLP pays twice.

VMEM budget per step (f32): x (bm x D) + wg/wu (D x bf) * 2 + wd (bf x D)
+ out (bm x D). With bm=256, bf=512, D=4096: 4+8+8+8+4 = 32 MiB/2... use
bm=128, bf=256 for 16 MiB-class VMEM (defaults below are CI-small; the
TPU launcher picks per-arch tiles).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(x_ref, wg_ref, wu_ref, wd_ref, out_ref, *, activation: str):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    x = x_ref[...]                     # (bm, D)
    g = jnp.dot(x, wg_ref[...], preferred_element_type=jnp.float32)
    u = jnp.dot(x, wu_ref[...], preferred_element_type=jnp.float32)
    if activation == "silu":
        act = g * jax.nn.sigmoid(g)
    elif activation == "gelu":
        act = jax.nn.gelu(g, approximate=True)
    else:
        raise ValueError(activation)
    h = (act * u).astype(x.dtype)      # (bm, bf) stays in VMEM
    out_ref[...] += jnp.dot(h, wd_ref[...], preferred_element_type=jnp.float32
                            ).astype(out_ref.dtype)


def fused_swiglu_pallas(
    x: jnp.ndarray,       # (M, D)
    w_gate: jnp.ndarray,  # (D, F)
    w_up: jnp.ndarray,    # (D, F)
    w_down: jnp.ndarray,  # (F, D)
    *,
    activation: str = "silu",
    block_m: int = 128,
    block_f: int = 256,
    interpret: bool,
) -> jnp.ndarray:
    m, d = x.shape
    f = w_gate.shape[1]
    assert m % block_m == 0 and f % block_f == 0, (m, f, block_m, block_f)
    grid = (m // block_m, f // block_f)
    return pl.pallas_call(
        functools.partial(_kernel, activation=activation),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, d), lambda i, j: (i, 0)),
            pl.BlockSpec((d, block_f), lambda i, j: (0, j)),
            pl.BlockSpec((d, block_f), lambda i, j: (0, j)),
            pl.BlockSpec((block_f, d), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((block_m, d), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((m, d), x.dtype),
        interpret=interpret,
    )(x, w_gate, w_up, w_down)

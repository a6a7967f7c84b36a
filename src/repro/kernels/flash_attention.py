"""Flash attention (online softmax) Pallas kernel — beyond-paper addition
for the LM substrate's prefill path (EXPERIMENTS.md §Perf).

Chunked attention with running (max, sum) renormalization so the (Sq x Sk)
logit matrix never materializes in HBM. Grid (B*H, Sq/bq, Sk/bk); the KV
axis is the innermost (accumulation) dimension. Causal blocks that are
fully masked are skipped via @pl.when on the block indices.

Scratch (VMEM): acc (bq, D) f32, m/l (bq, 128) f32 running statistics.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref,
            *, scale: float, causal: bool, block_q: int, block_k: int,
            num_k_blocks: int):
    qi = pl.program_id(1)
    kj = pl.program_id(2)

    @pl.when(kj == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    def _body():
        q = q_ref[0]                      # (bq, D)
        k = k_ref[0]                      # (bk, D)
        v = v_ref[0]                      # (bk, D)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale                          # (bq, bk)
        if causal:
            rows = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 0)
            cols = kj * block_k + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 1)
            s = jnp.where(rows >= cols, s, NEG_INF)
        m_prev = m_ref[:, :1]                         # (bq, 1)
        m_cur = jnp.max(s, axis=-1, keepdims=True)    # (bq, 1)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)                        # (bq, bk)
        alpha = jnp.exp(m_prev - m_new)               # (bq, 1)
        l_new = alpha * l_ref[:, :1] + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    if causal:
        # skip blocks strictly above the diagonal
        pl.when(qi * block_q + block_q - 1 >= kj * block_k)(_body)
    else:
        _body()

    @pl.when(kj == num_k_blocks - 1)
    def _finish():
        l = l_ref[:, :1]
        o_ref[0] = (acc_ref[...] / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


def flash_attention_pallas(
    q: jnp.ndarray,  # (BH, Sq, D)
    k: jnp.ndarray,  # (BH, Sk, D)
    v: jnp.ndarray,  # (BH, Sk, D)
    *,
    causal: bool = True,
    scale: float | None = None,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool,
) -> jnp.ndarray:
    bh, sq, d = q.shape
    sk = k.shape[1]
    assert sq % block_q == 0 and sk % block_k == 0
    if scale is None:
        scale = float(1.0 / (d ** 0.5))
    grid = (bh, sq // block_q, sk // block_k)
    return pl.pallas_call(
        functools.partial(
            _kernel, scale=scale, causal=causal, block_q=block_q,
            block_k=block_k, num_k_blocks=sk // block_k,
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),   # acc
            pltpu.VMEM((block_q, 128), jnp.float32),  # running max
            pltpu.VMEM((block_q, 128), jnp.float32),  # running sum
        ],
        interpret=interpret,
    )(q, k, v)

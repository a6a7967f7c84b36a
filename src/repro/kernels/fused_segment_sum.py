"""Fused sorted-segment reduction (the GNN scatter bottleneck, C2).

``atom_conv`` / ``bond_conv`` / the direct force head all reduce edge
messages into node rows: ``out[s] = sum_{e : seg(e)=s} values[e]``.  The
reference lowering is an unsorted scatter-add (atomics on GPU,
serialization on TPU); the one-hot matmul fallback is deterministic but
O(E*S) FLOPs.  This kernel exploits the sorted-segment batch layout
(DESIGN.md §1) instead:

  - the grid walks *segment-row tiles* (``block_rows`` rows per program);
  - one CSR row pointer per tile arrives via scalar prefetch, so each
    program knows its edge range ``[offsets[r0], offsets[r0 + block_rows])``
    before it runs;
  - edges are consumed in ``chunk``-aligned slices; each slice builds a
    *windowed* one-hot ``(block_rows, chunk)`` from the chunk's lane-dense
    id row — bounded because sorted edges of a row tile can only name
    segments inside that tile — and one MXU contraction accumulates
    ``(block_rows, D)`` partial sums in VMEM.

Every row is owned by exactly one program, so the reduction is
deterministic (fixed chunk order, no atomics, no cross-tile carries) and
the padded edge tail is never touched (``offsets[-1]`` == real edges).

Precision (DESIGN.md §4): ``values`` may be bf16 — the windowed one-hot
is built at the operand dtype, the MXU contraction accumulates f32
(``preferred_element_type``), and the output buffer is f32; the ``ops``
wrapper casts the sliced result back to the operand dtype.

Residency tiers (DESIGN.md §9): with ``residency="vmem"`` values/segment
ids are kept whole-array resident — fine for interpret mode (CI) and for
CHGNet-scale bond tensors on TPU (~bond_cap x dim f32).
``residency="hbm"`` leaves both in HBM (``pl.ANY``) and streams each
chunk through ping/pong VMEM scratch with double-buffered async copies
(``fused_message_passing._stream_loop``), so edge tensors that outgrow
VMEM — 10k+-atom structures — reduce without whole-array residency.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(offs_ref, seg_ref, val_ref, out_ref, *, block_rows: int,
            chunk: int):
    # windowed one-hot shared with the message-passing megakernels, which
    # generalize this reduction (DESIGN.md §3)
    from .fused_message_passing import _id_row, _mm, _window_onehot

    i = pl.program_id(0)
    r0 = i * block_rows
    start = offs_ref[i]
    end = offs_ref[i + 1]
    out_ref[...] = jnp.zeros(out_ref.shape, out_ref.dtype)

    def body(k, carry):
        base = k * chunk  # chunk-aligned, so slices never straddle the cap
        v = val_ref[pl.ds(base, chunk), :]                     # (chunk, D)
        onehot = _window_onehot(_id_row(seg_ref, k), r0, start, end, base,
                                chunk, block_rows)     # (block_rows, chunk)
        out_ref[...] += _mm(onehot, v).astype(out_ref.dtype)
        return carry

    jax.lax.fori_loop(start // chunk, pl.cdiv(end, chunk), body, 0)


def _kernel_hbm(offs_ref, seg_ref, val_ref, out_ref, seg_scr, val_scr,
                seg_sem, val_sem, *, block_rows: int, chunk: int):
    """HBM-residency tier (DESIGN.md §9): ids/values stream through
    ping/pong scratch, each next chunk's DMA overlapping the current
    chunk's windowed-one-hot contraction."""
    from .fused_message_passing import _mm, _stream_loop, _window_onehot

    i = pl.program_id(0)
    r0 = i * block_rows
    start = offs_ref[i]
    end = offs_ref[i + 1]
    out_ref[...] = jnp.zeros(out_ref.shape, out_ref.dtype)
    streams = ((seg_ref, seg_scr, seg_sem), (val_ref, val_scr, val_sem))

    def body(k, slot):
        onehot = _window_onehot(seg_scr[slot], r0, start, end, k * chunk,
                                chunk, block_rows)
        out_ref[...] += _mm(onehot, val_scr[slot]).astype(out_ref.dtype)

    _stream_loop(start // chunk, pl.cdiv(end, chunk), streams, body)


def fused_segment_sum_pallas(
    values: jnp.ndarray,   # (E, D) f32/bf16, E % chunk == 0, D % 128 == 0
    seg_ids: jnp.ndarray,  # (E/chunk, chunk) int32 id rows, sorted over
                           # the real prefix
    offsets: jnp.ndarray,  # (S + 1,) int32 CSR row pointers, S % block_rows == 0
    *,
    interpret: bool,
    block_rows: int = 8,
    chunk: int = 256,
    residency: str = "vmem",
) -> jnp.ndarray:
    from .fused_message_passing import (
        _any_spec, _blocks, _check_residency, _compiler_params, _id_scratch,
        _tile_offsets,
    )

    e, d = values.shape
    s = offsets.shape[0] - 1
    hbm = _check_residency(residency)
    assert e % chunk == 0 and seg_ids.shape == (e // chunk, chunk), \
        (e, seg_ids.shape, chunk)
    assert s % block_rows == 0, (s, block_rows)
    grid = (s // block_rows,)
    if hbm:
        in_specs = [_any_spec(), _any_spec()]
        operands = (_blocks(seg_ids, 1), _blocks(values, chunk))
        scratch_shapes = [
            _id_scratch(chunk),
            pltpu.VMEM((2, chunk, d), values.dtype),
        ] + [pltpu.SemaphoreType.DMA((2,))] * 2
        kernel = functools.partial(_kernel_hbm, block_rows=block_rows,
                                   chunk=chunk)
    else:
        in_specs = [
            pl.BlockSpec(seg_ids.shape, lambda i, offs: (0, 0)),
            pl.BlockSpec((e, d), lambda i, offs: (0, 0)),
        ]
        operands = (seg_ids, values)
        scratch_shapes = []
        kernel = functools.partial(_kernel, block_rows=block_rows,
                                   chunk=chunk)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((block_rows, d), lambda i, offs: (i, 0)),
        scratch_shapes=scratch_shapes,
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((s, d), jnp.float32),
        compiler_params=_compiler_params(),
        interpret=interpret,
    )(_tile_offsets(offsets, block_rows), *operands)

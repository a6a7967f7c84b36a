"""Fused gather -> GatedMLP -> reduce message-passing megakernels (C2+C4).

The unfused hot path materializes, per interaction block and per layer, the
gathered concat tensors (``(E, 3D)`` for atom_conv, ``(A_ang, 4D)`` for
bond_conv) and the ``(E, D)`` message tensors in HBM — and autodiff then
*saves all of them* for the backward pass.  These kernels fuse the whole
message path over the sorted-CSR rows (DESIGN.md §1, §3) so none of those
intermediates ever exists outside VMEM:

  - the grid walks *destination-row tiles* (``block_rows`` rows per
    program); each program's CSR edge range ``[start, end)`` arrives via
    scalar prefetch (one pointer per tile, ``offsets[::block_rows]``), so
    each program knows its edge range before it runs (same ownership
    model as ``fused_segment_sum``: every row belongs to exactly one
    program, the reduction is deterministic, the padded tail is never
    touched);
  - edges are consumed in ``chunk``-aligned slices.  Per slice, operand
    rows are gathered on the MXU: the *destination-side* operand (``v`` of
    the center atom for atom_conv; ``e``/``e_b`` of the center bond for
    bond_conv) via a windowed one-hot against the row tile — bounded
    because sorted edges of a tile only name segments inside it — and the
    *remote* operands (``v[bond_nbr]``, ``v[center]``/``e[angle_ik]``) via
    a one-hot against the feature table, walked in ``gather_tile``-row
    windows;
  - the concat-GEMM is algebraically split per operand
    (``concat(xs) @ W == sum_k xs[k] @ W_k``), so even in VMEM the packed
    concat row is never built; the packed ``[Wc ‖ Wg]`` GEMM halves share
    one masked-LayerNorm + sigmoid epilogue (paper Fig. 3);
  - with the undirected bond store (``mirror=True``, DESIGN.md §5) the
    envelope operands join a fourth, *mirror-indirected* class: ``e_a`` /
    ``e_b`` live in Eu-row undirected tables and are gathered per edge
    chunk through the ``bond_pair`` mirror-map ids with the same tiled
    one-hot mechanism as remote operands — the directed (E, D) envelope
    expansions never exist in HBM or VMEM;
  - envelope weights are applied in-register and the weighted messages are
    accumulated straight into the destination tile with the windowed
    one-hot (one more MXU contraction).

Id streams are *lane-dense rows*: an int32 id array of ``n`` entries is
passed as ``(n // chunk, chunk)``, one edge chunk per row, so a chunk's ids
are one ``(1, chunk)`` vector along the 128-lane axis.  An ``(n, 1)``
column would be laid out 128 lanes wide on the chip (512 bytes per id)
and could only be sliced or DMA'd in lane-misaligned pieces.  Every
one-hot is therefore built *transposed* — ``(rows, chunk)``, rows along
sublanes, edges along lanes — and contracted over its leading axis for a
gather (``_mm_t``) or its trailing axis for a scatter (``_mm``).

Feature lanes are padded to 128 by the ``ops`` wrappers; LayerNorm masks
the padded lanes (static ``d_real``), so padding never biases statistics.

Residency tiers (DESIGN.md §9): with ``residency="vmem"`` the feature
tables (``v``, ``e``, ``e_b``, edge payloads, id rows) are whole-array
VMEM-resident.  ``residency="hbm"`` leaves them in HBM (``pl.ANY`` memory
space) as leading-axis blocks (``_blocks``) and streams them through
ping/pong VMEM scratch with double-buffered ``pltpu.make_async_copy``
DMAs keyed off the scalar-prefetched CSR offsets: edge-contiguous operands
move in ``chunk``-row blocks (``_stream_loop``) and gathered tables in
``gather_tile``-row windows (``_gather_rows_hbm``), each next block's DMA
overlapping the current block's one-hot-gather + GEMM + epilogue — batch
capacity is then bounded by HBM, not by VMEM (10k+-atom structures).
Every launch passes an explicit ``vmem_limit_bytes`` (``VMEM_LIMIT_BYTES``)
so the compiler's scoped-VMEM default never decides what fits.

The backward story (recompute-in-kernel, "redundancy bypass") lives in the
``ops`` custom VJPs: the forward saves *only the operands*, never the
messages, and the backward rematerializes the message path (DESIGN.md §3).

Precision (DESIGN.md §4): feature/weight tables may be bf16 (halving
their VMEM residency — the binding constraint called out above).  Every
MXU contraction accumulates f32 (``_mm``/``_mm_t``), one-hot gather
matrices are cast to the table dtype (lossless 0/1), LayerNorm statistics
and envelope products are evaluated in f32, and the f32 destination
accumulator is cast back to the operand dtype only by the ``ops`` wrapper
slice.  The recompute-in-backward loops accumulate cotangents in f32 and
cast to the operand dtypes at the end.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Scoped-VMEM ceiling handed to every launch.  A v5e TensorCore has
# 128 MiB of VMEM; the compiler's default scope is far smaller, so without
# an explicit limit the vmem tier fails to compile well inside the
# ``auto`` budget (``ops.vmem_budget_bytes``).  The headroom above the
# budget covers double-buffered blocks and the in-kernel temporaries.
VMEM_LIMIT_BYTES = 96 * 2 ** 20

# x_hat lane holding the bond distance in the force+virial readout: lanes
# 0..2 carry the unit vector, the rest of the 128-lane row is padding
_DIST_LANE = 3


def _compiler_params():
    return pltpu.CompilerParams(vmem_limit_bytes=VMEM_LIMIT_BYTES)


def _mm(a, b):
    """a @ b on the MXU with f32 in-register accumulation.

    ``a`` is cast to ``b``'s dtype first (DESIGN.md §4): the right operand
    is the VMEM feature/weight table whose dtype the policy picked, and
    the left operand is either a 0/1 one-hot (exact at any float dtype) or
    a gather result that *holds* values of ``b``'s dtype — so the cast is
    lossless while keeping both MXU inputs at one dtype."""
    return jax.lax.dot_general(
        a.astype(b.dtype), b, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)


def _mm_t(a, b):
    """a.T @ b (contract rows) on the MXU with f32 accumulation."""
    return jax.lax.dot_general(
        a.astype(b.dtype), b, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)


def _masked_ln(x, scale, bias, d_real: int, eps=1e-5):
    """LayerNorm over the first ``d_real`` lanes; padded lanes stay zero.

    ``x`` arrives f32 from the accumulating GEMM; statistics stay f32."""
    scale = scale.astype(jnp.float32)
    bias = bias.astype(jnp.float32)
    cols = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    m = (cols < d_real).astype(x.dtype)
    cnt = jnp.float32(d_real)
    mu = jnp.sum(x * m, axis=-1, keepdims=True) / cnt
    var = jnp.sum(jnp.square(x - mu) * m, axis=-1, keepdims=True) / cnt
    return ((x - mu) * jax.lax.rsqrt(var + eps) * scale + bias) * m


def _gated_epilogue(y, lns, lnb, hp: int, d_real: int):
    """Packed-GEMM epilogue: both LNs + silu/sigmoid gating (Fig. 3b)."""
    core = _masked_ln(y[:, :hp], lns[0, :hp], lnb[0, :hp], d_real)
    gate = _masked_ln(y[:, hp:], lns[0, hp:], lnb[0, hp:], d_real)
    # silu(core) = core * sigmoid(core): one kind of sigmoid evaluation
    return (core * jax.nn.sigmoid(core)) * jax.nn.sigmoid(gate)


def _edge_valid(base, start, end, chunk: int):
    """(1, chunk) mask of the chunk's edges inside this tile's [start, end)."""
    e_ids = base + jax.lax.broadcasted_iota(jnp.int32, (1, chunk), 1)
    return (e_ids >= start) & (e_ids < end)


def _window_onehot(seg, r0, start, end, base, chunk: int, block_rows: int):
    """(block_rows, chunk) one-hot of tile-row <- edge for a ``(1, chunk)``
    id row, zero outside [start, end).  ``_mm_t`` with it gathers the
    tile rows per edge; ``_mm`` scatters per-edge messages into the tile."""
    rows = jax.lax.broadcasted_iota(jnp.int32, (block_rows, chunk), 0)
    valid = _edge_valid(base, start, end, chunk)
    return ((seg - r0 == rows) & valid).astype(jnp.float32)


def _id_row(ref, k):
    """Chunk ``k``'s ids from a VMEM-resident ``(n // chunk, chunk)`` id
    array, as one lane-dense ``(1, chunk)`` row."""
    return ref[pl.ds(k, 1), :]


def _gather_rows(ids, table_refs, tile: int, bounds):
    """MXU row gather: ``[table[ids] for table in table_refs]``.

    ``ids`` is a ``(1, n)`` id row.  Walks the table in ``tile``-row
    windows (table rows must be a ``tile`` multiple — the ops wrappers
    pad) so the one-hot never exceeds ``(tile, n)`` — a full-table
    one-hot would put an O(rows x n) temp in VMEM.  Only the windows
    ``[lo, hi) = bounds`` that the chunk's ids fall in are walked
    (``_walk_windows``), so flops are O(n x span x D) with ``span`` the
    id range of one chunk — crystal-local for sorted batches — not the
    whole table.  Tables sharing the same ids (e/e_b in bond_conv) reuse
    one one-hot per window.
    """
    n = ids.shape[1]

    def body(t, accs):
        t0 = t * tile
        rows = t0 + jax.lax.broadcasted_iota(jnp.int32, (tile, n), 0)
        oh = (ids == rows).astype(jnp.float32)
        return tuple(
            acc + _mm_t(oh, ref[pl.ds(t0, tile), :])
            for acc, ref in zip(accs, table_refs)
        )

    init = tuple(
        jnp.zeros((n, ref.shape[1]), jnp.float32) for ref in table_refs)
    return jax.lax.fori_loop(bounds[0], bounds[1], body, init)


def _walk_windows(walks, tile: int):
    """Per-chunk ``[lo, hi)`` range of ``tile``-row windows for each table
    walk, flattened to ``(n_walks * n_chunks * 2,)`` int32 for scalar
    prefetch (``_walk_bounds`` reads it back).

    ``walks`` lists, per walk, the ``(n_chunks, chunk)`` id rows whose
    gathers share that walk; the range is the union over them.  Sorted
    batches keep a chunk's ids inside one or two crystals, so the range is
    a few windows wide where walking the whole table would cost
    ``rows / tile`` windows per chunk.  Padded ids (0) can widen only the
    one chunk that straddles the real/padded boundary."""
    ranges = []
    for ids_group in walks:
        lo = jnp.min(jnp.stack([jnp.min(x, axis=1) for x in ids_group]),
                     axis=0) // tile
        hi = jnp.max(jnp.stack([jnp.max(x, axis=1) for x in ids_group]),
                     axis=0) // tile + 1
        ranges.append(jnp.stack([lo, hi], axis=-1))
    return jnp.stack(ranges).astype(jnp.int32).reshape(-1)


def _walk_bounds(win_ref, walk: int, k, n_chunks: int):
    """Chunk ``k``'s ``(lo, hi)`` window range of walk ``walk``."""
    j = 2 * (walk * n_chunks + k)
    return win_ref[j], win_ref[j + 1]


def _tile_offsets(offsets, block_rows: int):
    """Full CSR pointers (R + 1,) -> one pointer per row tile (R/br + 1,).

    Program ``i`` needs only ``offsets[i*br]`` and ``offsets[(i+1)*br]``;
    prefetching just those keeps the SMEM operand small (SMEM holds
    1 MiB, less than a batch-128 bond table's full pointer array)."""
    return offsets[::block_rows]


# ---------------------------------------------------------------------------
# HBM residency tier: double-buffered DMA streaming (DESIGN.md §9)
# ---------------------------------------------------------------------------
#
# With ``residency="hbm"`` the operand tables stay in HBM (``pl.ANY``
# in_specs) as leading-axis blocks (``_blocks``) and move through ping/pong
# VMEM scratch slots.  A "stream" is the triple (hbm_ref, scratch_ref,
# sem_ref) where scratch/sem carry a leading dim of 2 (the ping/pong
# slots).  Block k always lands in slot ``k % 2``, so starting block k+1
# before waiting on block k overlaps the next DMA with the current compute
# without ever racing a live slot: the slot k+1 targets was consumed one
# iteration ago.  DMAs slice only the untiled leading axis, so every copy
# is tile-aligned whatever the block's row count (one row for id blocks).

def _blocks(x, rows: int):
    """(n, ...) -> (n // rows, rows, ...): the DMA blocks of an HBM-resident
    operand (a free split of the leading axis)."""
    return x.reshape((x.shape[0] // rows, rows) + x.shape[1:])


def _stream_copies(streams, idx):
    """DMA descriptors moving block ``idx`` of each stream's HBM ref into
    its slot ``idx % 2`` scratch buffer."""
    slot = jax.lax.rem(idx, 2)
    return [
        pltpu.make_async_copy(hbm.at[idx], scr.at[slot], sem.at[slot])
        for hbm, scr, sem in streams
    ]


def _stream_loop(k0, k1, streams, body):
    """Double-buffered walk of blocks [k0, k1): warm-up starts block k0,
    then each iteration starts block k+1's DMA, waits on block k, and runs
    ``body(k, slot)`` — compute on slot k overlaps the k+1 transfer."""
    @pl.when(k0 < k1)
    def _warmup():
        for c in _stream_copies(streams, k0):
            c.start()

    def step(k, carry):
        @pl.when(k + 1 < k1)
        def _prefetch_next():
            for c in _stream_copies(streams, k + 1):
                c.start()
        for c in _stream_copies(streams, k):
            c.wait()
        body(k, jax.lax.rem(k, 2))
        return carry

    jax.lax.fori_loop(k0, k1, step, 0)


def _gather_rows_hbm(ids_list, tables, tile: int, bounds):
    """MXU row gather from HBM-resident tables (the ``residency="hbm"``
    counterpart of ``_gather_rows``).

    ``tables`` holds (hbm_ref, scratch_ref, sem_ref) streams sharing one
    window count (hbm refs are ``(rows // tile, tile, D)`` blocks); the
    ``tile``-row windows ``[lo, hi) = bounds`` flow through the ping/pong
    scratch double-buffered, the next window's DMA overlapping this
    window's one-hot contraction.  Returns ``[[table_j[ids_i] for j] for
    i]`` so callers with shared ids (e/e_b via angle_ik) or a shared table
    (the Eu e^b mirror table via pij/pik) pay for one table walk.
    """
    lo, hi = bounds
    n = ids_list[0].shape[1]

    @pl.when(lo < hi)
    def _warmup():
        for c in _stream_copies(tables, lo):
            c.start()

    def step(t, accs):
        @pl.when(t + 1 < hi)
        def _prefetch_next():
            for c in _stream_copies(tables, t + 1):
                c.start()
        slot = jax.lax.rem(t, 2)
        for c in _stream_copies(tables, t):
            c.wait()
        rows = t * tile + jax.lax.broadcasted_iota(jnp.int32, (tile, n), 0)
        return tuple(
            tuple(acc + _mm_t((ids == rows).astype(jnp.float32),
                              tables[j][1][slot])
                  for j, acc in enumerate(row))
            for ids, row in zip(ids_list, accs))

    init = tuple(
        tuple(jnp.zeros((n, t[1].shape[-1]), jnp.float32) for t in tables)
        for _ in ids_list)
    return jax.lax.fori_loop(lo, hi, step, init)


def _any_spec():
    """HBM-resident operand: no block shape, kernels DMA rows on demand."""
    return pl.BlockSpec(memory_space=pl.ANY)


def _id_scratch(chunk: int):
    """Ping/pong slots of one streamed ``(1, chunk)`` id block."""
    return pltpu.VMEM((2, 1, chunk), jnp.int32)


def _const_spec(shape):
    """A small operand (weights, biases) fetched whole at every step."""
    return pl.BlockSpec(shape, lambda i, *_: (0,) * len(shape))


def _row_block(i, *_):
    """Index map of a destination-row tile (any scalar-prefetch refs)."""
    return (i, 0)


def _check_residency(residency: str) -> bool:
    if residency not in ("vmem", "hbm"):
        raise ValueError(f"residency must be 'vmem' or 'hbm', "
                         f"got {residency!r}")
    return residency == "hbm"


# ---------------------------------------------------------------------------
# atom_conv megakernel: bonds -> atoms (Eq. 4 message path)
# ---------------------------------------------------------------------------

def _atom_conv_kernel(offs_ref, win_ref, seg_ref, nbr_ref, pair_ref,
                      v_full_ref, v_tile_ref, e_ref, ea_ref, w1_ref, w2_ref,
                      w3_ref, b_ref, lns_ref, lnb_ref, out_ref, *,
                      block_rows: int, chunk: int, d_real: int,
                      gather_tile: int, mirror: bool, und: bool,
                      n_chunks: int):
    i = pl.program_id(0)
    r0 = i * block_rows
    start = offs_ref[i]
    end = offs_ref[i + 1]
    out_ref[...] = jnp.zeros(out_ref.shape, out_ref.dtype)
    hp = b_ref.shape[-1] // 2

    def body(k, carry):
        base = k * chunk  # chunk-aligned, so slices never straddle the cap
        oh_w = _window_onehot(_id_row(seg_ref, k), r0, start, end, base,
                              chunk, block_rows)
        v_c = _mm_t(oh_w, v_tile_ref[...])        # gather v[bond_center]
        (v_n,) = _gather_rows(                    # gather v[bond_nbr]
            _id_row(nbr_ref, k), (v_full_ref,), gather_tile,
            _walk_bounds(win_ref, 0, k, n_chunks))
        # Mirror-indirected operand class (DESIGN.md §5): with the
        # undirected store, e^a lives in an Eu-row table and is gathered
        # through bond_pair — the directed (E, D) expansion never exists
        # in HBM or VMEM.  With the symmetric trunk (``und``, DESIGN.md
        # §10) ``e`` joins it: both tables share ONE window walk.
        if mirror and und:
            e_c, ea_c = _gather_rows(
                _id_row(pair_ref, k), (e_ref, ea_ref), gather_tile,
                _walk_bounds(win_ref, 1, k, n_chunks))
        else:
            e_c = e_ref[pl.ds(base, chunk), :]    # edge-contiguous slice
            if mirror:
                (ea_c,) = _gather_rows(
                    _id_row(pair_ref, k), (ea_ref,), gather_tile,
                    _walk_bounds(win_ref, 1, k, n_chunks))
            else:
                ea_c = ea_ref[pl.ds(base, chunk), :].astype(jnp.float32)
        # split concat-GEMM: [v_c ‖ v_n ‖ e] @ [Wc ‖ Wg] without the concat
        y = _mm(v_c, w1_ref[...]) + _mm(v_n, w2_ref[...]) \
            + _mm(e_c, w3_ref[...]) + b_ref[...].astype(jnp.float32)
        msg = _gated_epilogue(y, lns_ref, lnb_ref, hp, d_real)
        # envelope e^a_ij applied in-register at f32 (accum rule, §4)
        msg = msg * ea_c
        out_ref[...] += _mm(oh_w, msg).astype(out_ref.dtype)
        return carry

    jax.lax.fori_loop(start // chunk, pl.cdiv(end, chunk), body, 0)


def _atom_conv_kernel_hbm(offs_ref, win_ref, seg_ref, nbr_ref, pair_ref,
                          v_full_ref, v_tile_ref, e_ref, ea_ref, w1_ref,
                          w2_ref, w3_ref, b_ref, lns_ref, lnb_ref, out_ref,
                          *scratch, block_rows: int, chunk: int,
                          d_real: int, gather_tile: int, mirror: bool,
                          und: bool, n_chunks: int):
    """HBM-residency atom_conv (DESIGN.md §9): same math as
    ``_atom_conv_kernel`` but every large operand lives in HBM and streams
    through ping/pong scratch — edge payloads (seg/nbr/pair ids, ``e``,
    directed ``e_a``) in chunk blocks, the ``v`` table (and the Eu-row
    ``e_a`` — plus ``e`` under ``und`` — mirror tables) in gather_tile
    windows."""
    i = pl.program_id(0)
    r0 = i * block_rows
    start = offs_ref[i]
    end = offs_ref[i + 1]
    out_ref[...] = jnp.zeros(out_ref.shape, out_ref.dtype)
    hp = b_ref.shape[-1] // 2
    if mirror and und:
        (seg_scr, nbr_scr, pair_scr, v_gscr, e_gscr, ea_gscr,
         seg_sem, nbr_sem, pair_sem, v_gsem, e_gsem, ea_gsem) = scratch
        edge_streams = ((seg_ref, seg_scr, seg_sem),
                        (nbr_ref, nbr_scr, nbr_sem),
                        (pair_ref, pair_scr, pair_sem))
    elif mirror:
        (seg_scr, nbr_scr, pair_scr, e_scr, v_gscr, ea_gscr,
         seg_sem, nbr_sem, pair_sem, e_sem, v_gsem, ea_gsem) = scratch
        edge_streams = ((seg_ref, seg_scr, seg_sem),
                        (nbr_ref, nbr_scr, nbr_sem),
                        (pair_ref, pair_scr, pair_sem),
                        (e_ref, e_scr, e_sem))
    else:
        (seg_scr, nbr_scr, e_scr, ea_scr, v_gscr,
         seg_sem, nbr_sem, e_sem, ea_sem, v_gsem) = scratch
        edge_streams = ((seg_ref, seg_scr, seg_sem),
                        (nbr_ref, nbr_scr, nbr_sem),
                        (e_ref, e_scr, e_sem),
                        (ea_ref, ea_scr, ea_sem))

    def body(k, slot):
        oh_w = _window_onehot(seg_scr[slot], r0, start, end, k * chunk,
                              chunk, block_rows)
        v_c = _mm_t(oh_w, v_tile_ref[...])        # gather v[bond_center]
        ((v_n,),) = _gather_rows_hbm(             # gather v[bond_nbr]
            (nbr_scr[slot],), ((v_full_ref, v_gscr, v_gsem),), gather_tile,
            _walk_bounds(win_ref, 0, k, n_chunks))
        if mirror and und:
            # §10: Eu-resident e and e^a share one streamed window walk
            ((e_c, ea_c),) = _gather_rows_hbm(
                (pair_scr[slot],),
                ((e_ref, e_gscr, e_gsem), (ea_ref, ea_gscr, ea_gsem)),
                gather_tile, _walk_bounds(win_ref, 1, k, n_chunks))
        else:
            e_c = e_scr[slot]
        y = _mm(v_c, w1_ref[...]) + _mm(v_n, w2_ref[...]) \
            + _mm(e_c, w3_ref[...]) + b_ref[...].astype(jnp.float32)
        msg = _gated_epilogue(y, lns_ref, lnb_ref, hp, d_real)
        if mirror and not und:
            ((ea_c,),) = _gather_rows_hbm(
                (pair_scr[slot],), ((ea_ref, ea_gscr, ea_gsem),),
                gather_tile, _walk_bounds(win_ref, 1, k, n_chunks))
        elif not mirror:
            ea_c = ea_scr[slot].astype(jnp.float32)
        msg = msg * ea_c
        out_ref[...] += _mm(oh_w, msg).astype(out_ref.dtype)

    _stream_loop(start // chunk, pl.cdiv(end, chunk), edge_streams, body)


def fused_atom_conv_pallas(
    v: jnp.ndarray,        # (A, DP) f32, A % block_rows == 0, DP % 128 == 0
    e: jnp.ndarray,        # (E, DP) f32 — or (EU, DP) table (und)
    e_a: jnp.ndarray,      # (E, HP) envelope — or (EU, HP) table (mirror)
    seg: jnp.ndarray,      # (E/chunk, chunk) int32 bond_center id rows,
                           # sorted over the real prefix
    nbr: jnp.ndarray,      # (E/chunk, chunk) int32 bond_nbr id rows
    pair: jnp.ndarray,     # (E/chunk, chunk) int32 bond_pair (mirror; else
                           # any dummy)
    offsets: jnp.ndarray,  # (A + 1,) int32 CSR row pointers
    w1: jnp.ndarray, w2: jnp.ndarray, w3: jnp.ndarray,  # (DP, 2*HP) each
    b: jnp.ndarray,        # (1, 2*HP)
    ln_scale: jnp.ndarray, ln_bias: jnp.ndarray,        # (1, 2*HP)
    *,
    d_real: int,
    interpret: bool,
    block_rows: int = 8,
    chunk: int = 256,
    gather_tile: int = 256,
    mirror: bool = False,
    und: bool = False,
    residency: str = "vmem",
) -> jnp.ndarray:
    a_rows, dp = v.shape
    n_edges = seg.size         # directed bond rows driving the chunk walk
    e_rows = e.shape[0]        # == n_edges, or the Eu table rows under und
    ea_rows = e_a.shape[0]
    hp2 = b.shape[-1]
    hp = hp2 // 2
    hbm = _check_residency(residency)
    assert seg.shape[1] == chunk, (seg.shape, chunk)
    assert a_rows % block_rows == 0, (a_rows, block_rows)
    assert a_rows % gather_tile == 0, (a_rows, gather_tile)
    if und:  # §10: e is an Eu-row table gathered through bond_pair
        assert mirror, "und requires the mirror operand class"
        assert e_rows % gather_tile == 0, (e_rows, gather_tile)
    else:
        assert e_rows == n_edges, (e_rows, n_edges)
    if mirror:  # the e^a table is walked in gather_tile windows
        assert ea_rows % gather_tile == 0, (ea_rows, gather_tile)
    else:
        assert ea_rows == n_edges, (ea_rows, n_edges)
    grid = (a_rows // block_rows,)
    tile_spec = pl.BlockSpec((block_rows, dp), lambda i, *_: (i, 0))
    if hbm:
        # streamed operands stay in HBM; only the destination tile, the
        # weights, and the ping/pong scratch live in VMEM (DESIGN.md §9)
        table_specs = [_any_spec()] * 4 + [tile_spec] + [_any_spec()] * 2
        ids = [_blocks(x, 1) for x in (seg, nbr, pair)]
        v_g = _blocks(v, gather_tile)
        # edge-contiguous payloads move in chunk blocks, gather tables
        # (mirror / und) in gather_tile windows
        e_t = _blocks(e, gather_tile if und else chunk)
        ea_t = _blocks(e_a, gather_tile if mirror else chunk)
        operands = (*ids, v_g, v, e_t, ea_t)
        id_scr = [_id_scratch(chunk)] * (3 if mirror else 2)
        if mirror and und:
            scratch_shapes = id_scr + [
                pltpu.VMEM((2, gather_tile, dp), v.dtype),  # v windows
                pltpu.VMEM((2, gather_tile, dp), e.dtype),  # e windows
                pltpu.VMEM((2, gather_tile, hp), e_a.dtype),  # e^a windows
            ] + [pltpu.SemaphoreType.DMA((2,))] * 6
        elif mirror:
            scratch_shapes = id_scr + [
                pltpu.VMEM((2, chunk, dp), e.dtype),        # e blocks
                pltpu.VMEM((2, gather_tile, dp), v.dtype),  # v windows
                pltpu.VMEM((2, gather_tile, hp), e_a.dtype),  # e^a windows
            ] + [pltpu.SemaphoreType.DMA((2,))] * 6
        else:
            scratch_shapes = id_scr + [
                pltpu.VMEM((2, chunk, dp), e.dtype),        # e blocks
                pltpu.VMEM((2, chunk, hp), e_a.dtype),      # e^a blocks
                pltpu.VMEM((2, gather_tile, dp), v.dtype),  # v windows
            ] + [pltpu.SemaphoreType.DMA((2,))] * 5
        kernel = _atom_conv_kernel_hbm
    else:
        whole = lambda x: pl.BlockSpec(x.shape, lambda i, *_: (0, 0))
        table_specs = [whole(seg), whole(nbr), whole(pair), whole(v),
                       tile_spec, whole(e), whole(e_a)]
        operands = (seg, nbr, pair, v, v, e, e_a)
        scratch_shapes = []
        kernel = _atom_conv_kernel
    kernel = functools.partial(
        kernel, block_rows=block_rows, chunk=chunk, d_real=d_real,
        gather_tile=gather_tile, mirror=mirror, und=und,
        n_chunks=seg.shape[0])
    # gather walks: 0 = v[nbr], 1 = the Eu mirror tables via pair
    win = _walk_windows([[nbr], [pair]] if mirror else [[nbr]],
                        gather_tile)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=grid,
        in_specs=table_specs + [_const_spec((dp, hp2))] * 3
        + [_const_spec((1, hp2))] * 3,
        out_specs=pl.BlockSpec((block_rows, hp), _row_block),
        scratch_shapes=scratch_shapes,
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((a_rows, hp), jnp.float32),
        compiler_params=_compiler_params(),
        interpret=interpret,
    )(_tile_offsets(offsets, block_rows), win, *operands, w1, w2, w3, b,
      ln_scale, ln_bias)


# ---------------------------------------------------------------------------
# bond_conv megakernel: angles -> bonds (Eq. 5 message path)
# ---------------------------------------------------------------------------

def _bond_conv_kernel(offs_ref, win_ref, seg_ref, ik_ref, ctr_ref, pij_ref,
                      pik_ref, v_ref, e_full_ref, e_tile_ref, eb_full_ref,
                      eb_tile_ref, a_ref, w1_ref, w2_ref, w3_ref, w4_ref,
                      b_ref, lns_ref, lnb_ref, out_ref, *, block_rows: int,
                      chunk: int, d_real: int, gather_tile: int,
                      mirror: bool, n_chunks: int):
    i = pl.program_id(0)
    r0 = i * block_rows
    start = offs_ref[i]
    end = offs_ref[i + 1]
    out_ref[...] = jnp.zeros(out_ref.shape, out_ref.dtype)
    hp = b_ref.shape[-1] // 2

    def body(k, carry):
        base = k * chunk
        oh_w = _window_onehot(_id_row(seg_ref, k), r0, start, end, base,
                              chunk, block_rows)         # angle_ij
        e_ij = _mm_t(oh_w, e_tile_ref[...])      # gather e[angle_ij]
        if mirror:
            # mirror-indirected operand class (DESIGN.md §5): e^b lives in
            # an Eu-row table; BOTH envelope factors gather through the
            # precomputed bond_pair[angle_*] ids — the windowed one-hot no
            # longer applies because pair ids are not tile-local.
            (e_ik,) = _gather_rows(
                _id_row(ik_ref, k), (e_full_ref,), gather_tile,
                _walk_bounds(win_ref, 0, k, n_chunks))
            eb_win = _walk_bounds(win_ref, 2, k, n_chunks)
            (eb_ij,) = _gather_rows(
                _id_row(pij_ref, k), (eb_full_ref,), gather_tile, eb_win)
            (eb_ik,) = _gather_rows(
                _id_row(pik_ref, k), (eb_full_ref,), gather_tile, eb_win)
        else:
            eb_ij = _mm_t(oh_w, eb_tile_ref[...])  # gather e_b[angle_ij]
            # e / e_b share angle_ik: one tiled one-hot gathers both
            e_ik, eb_ik = _gather_rows(
                _id_row(ik_ref, k), (e_full_ref, eb_full_ref), gather_tile,
                _walk_bounds(win_ref, 0, k, n_chunks))
        (v_c,) = _gather_rows(                   # gather v[center]
            _id_row(ctr_ref, k), (v_ref,), gather_tile,
            _walk_bounds(win_ref, 1, k, n_chunks))
        a_c = a_ref[pl.ds(base, chunk), :]       # edge-contiguous slice
        y = _mm(v_c, w1_ref[...]) + _mm(e_ij, w2_ref[...]) \
            + _mm(e_ik, w3_ref[...]) + _mm(a_c, w4_ref[...]) \
            + b_ref[...].astype(jnp.float32)
        msg = _gated_epilogue(y, lns_ref, lnb_ref, hp, d_real)
        msg = msg * eb_ij * eb_ik  # envelopes are f32 gather results (§4)
        out_ref[...] += _mm(oh_w, msg).astype(out_ref.dtype)
        return carry

    jax.lax.fori_loop(start // chunk, pl.cdiv(end, chunk), body, 0)


def _bond_conv_kernel_hbm(offs_ref, win_ref, seg_ref, ik_ref, ctr_ref,
                          pij_ref, pik_ref, v_ref, e_full_ref, e_tile_ref,
                          eb_full_ref, eb_tile_ref, a_ref, w1_ref, w2_ref,
                          w3_ref, w4_ref, b_ref, lns_ref, lnb_ref, out_ref,
                          *scratch, block_rows: int, chunk: int,
                          d_real: int, gather_tile: int, mirror: bool,
                          n_chunks: int):
    """HBM-residency bond_conv (DESIGN.md §9): angle payloads (ids + ``a``)
    stream in chunk blocks; the ``v``/``e`` tables (and the Eu-row ``e^b``
    mirror table — its pij/pik gathers share ONE window walk) stream in
    gather_tile windows.  The destination e-tile (and the non-mirror
    eb-tile, both ``block_rows`` rows) stay VMEM block operands."""
    i = pl.program_id(0)
    r0 = i * block_rows
    start = offs_ref[i]
    end = offs_ref[i + 1]
    out_ref[...] = jnp.zeros(out_ref.shape, out_ref.dtype)
    hp = b_ref.shape[-1] // 2
    if mirror:
        (seg_scr, ik_scr, ctr_scr, pij_scr, pik_scr, a_scr,
         v_gscr, e_gscr, eb_gscr,
         seg_sem, ik_sem, ctr_sem, pij_sem, pik_sem, a_sem,
         v_gsem, e_gsem, eb_gsem) = scratch
        edge_streams = ((seg_ref, seg_scr, seg_sem),
                        (ik_ref, ik_scr, ik_sem),
                        (ctr_ref, ctr_scr, ctr_sem),
                        (pij_ref, pij_scr, pij_sem),
                        (pik_ref, pik_scr, pik_sem),
                        (a_ref, a_scr, a_sem))
    else:
        (seg_scr, ik_scr, ctr_scr, a_scr, v_gscr, e_gscr, eb_gscr,
         seg_sem, ik_sem, ctr_sem, a_sem,
         v_gsem, e_gsem, eb_gsem) = scratch
        edge_streams = ((seg_ref, seg_scr, seg_sem),
                        (ik_ref, ik_scr, ik_sem),
                        (ctr_ref, ctr_scr, ctr_sem),
                        (a_ref, a_scr, a_sem))

    def body(k, slot):
        oh_w = _window_onehot(seg_scr[slot], r0, start, end, k * chunk,
                              chunk, block_rows)         # angle_ij
        e_ij = _mm_t(oh_w, e_tile_ref[...])      # gather e[angle_ij]
        if mirror:
            ((e_ik,),) = _gather_rows_hbm(
                (ik_scr[slot],), ((e_full_ref, e_gscr, e_gsem),),
                gather_tile, _walk_bounds(win_ref, 0, k, n_chunks))
            # both Eu envelope factors share one walk of the mirror table
            ((eb_ij,), (eb_ik,)) = _gather_rows_hbm(
                (pij_scr[slot], pik_scr[slot]),
                ((eb_full_ref, eb_gscr, eb_gsem),), gather_tile,
                _walk_bounds(win_ref, 2, k, n_chunks))
        else:
            eb_ij = _mm_t(oh_w, eb_tile_ref[...])  # gather e_b[angle_ij]
            # e / e_b share angle_ik: one window walk gathers both
            ((e_ik, eb_ik),) = _gather_rows_hbm(
                (ik_scr[slot],),
                ((e_full_ref, e_gscr, e_gsem),
                 (eb_full_ref, eb_gscr, eb_gsem)), gather_tile,
                _walk_bounds(win_ref, 0, k, n_chunks))
        ((v_c,),) = _gather_rows_hbm(             # gather v[center]
            (ctr_scr[slot],), ((v_ref, v_gscr, v_gsem),), gather_tile,
            _walk_bounds(win_ref, 1, k, n_chunks))
        a_c = a_scr[slot]
        y = _mm(v_c, w1_ref[...]) + _mm(e_ij, w2_ref[...]) \
            + _mm(e_ik, w3_ref[...]) + _mm(a_c, w4_ref[...]) \
            + b_ref[...].astype(jnp.float32)
        msg = _gated_epilogue(y, lns_ref, lnb_ref, hp, d_real)
        msg = msg * eb_ij * eb_ik
        out_ref[...] += _mm(oh_w, msg).astype(out_ref.dtype)

    _stream_loop(start // chunk, pl.cdiv(end, chunk), edge_streams, body)


def fused_bond_conv_pallas(
    v: jnp.ndarray,        # (A, DP) f32 atom features
    e: jnp.ndarray,        # (B, DP) f32 bond features, B % block_rows == 0
    a: jnp.ndarray,        # (E, DP) f32 angle features, E % chunk == 0
    e_b: jnp.ndarray,      # (B, HP) envelope — or (EU, HP) table (mirror)
    seg: jnp.ndarray,      # (E/chunk, chunk) int32 angle_ij id rows,
                           # sorted over the real prefix
    ik: jnp.ndarray,       # (E/chunk, chunk) int32 angle_ik
    ctr: jnp.ndarray,      # (E/chunk, chunk) int32 bond_center[angle_ij]
    pij: jnp.ndarray,      # (E/chunk, chunk) int32 bond_pair[angle_ij]
                           # (mirror; else dummy)
    pik: jnp.ndarray,      # (E/chunk, chunk) int32 bond_pair[angle_ik]
                           # (mirror; else dummy)
    offsets: jnp.ndarray,  # (B + 1,) int32 CSR row pointers
    w1: jnp.ndarray, w2: jnp.ndarray, w3: jnp.ndarray, w4: jnp.ndarray,
    b: jnp.ndarray,        # (1, 2*HP)
    ln_scale: jnp.ndarray, ln_bias: jnp.ndarray,        # (1, 2*HP)
    *,
    d_real: int,
    interpret: bool,
    block_rows: int = 8,
    chunk: int = 256,
    gather_tile: int = 256,
    mirror: bool = False,
    residency: str = "vmem",
) -> jnp.ndarray:
    a_rows, dp = v.shape
    b_rows = e.shape[0]
    e_rows = a.shape[0]
    eb_rows = e_b.shape[0]
    hp2 = b.shape[-1]
    hp = hp2 // 2
    hbm = _check_residency(residency)
    assert e_rows % chunk == 0 and seg.shape == (e_rows // chunk, chunk), \
        (e_rows, seg.shape, chunk)
    assert b_rows % block_rows == 0, (b_rows, block_rows)
    assert b_rows % gather_tile == 0, (b_rows, gather_tile)
    assert a_rows % gather_tile == 0, (a_rows, gather_tile)
    if mirror:
        # the e^b table is walked in gather_tile windows; its unused tile
        # view (pinned at block 0 below) still needs one whole block
        assert eb_rows % gather_tile == 0, (eb_rows, gather_tile)
        assert eb_rows >= block_rows, (eb_rows, block_rows)
    else:
        assert eb_rows == b_rows, (eb_rows, b_rows)
    grid = (b_rows // block_rows,)
    e_tile_spec = pl.BlockSpec((block_rows, dp), lambda i, *_: (i, 0))
    eb_tile_spec = pl.BlockSpec(
        (block_rows, hp),
        (lambda i, *_: (i, 0)) if not mirror else (lambda i, *_: (0, 0)))
    ids = (seg, ik, ctr, pij, pik)
    if hbm:
        # ids + angle features + all three gather tables stay in HBM;
        # only the block_rows-row destination tiles remain VMEM operands
        table_specs = ([_any_spec()] * 7 + [e_tile_spec, _any_spec(),
                                            eb_tile_spec, _any_spec()])
        operands = (*[_blocks(x, 1) for x in ids],
                    _blocks(v, gather_tile), _blocks(e, gather_tile), e,
                    _blocks(e_b, gather_tile), e_b, _blocks(a, chunk))
        gather_scrs = [
            pltpu.VMEM((2, gather_tile, dp), v.dtype),    # v windows
            pltpu.VMEM((2, gather_tile, dp), e.dtype),    # e windows
            pltpu.VMEM((2, gather_tile, hp), e_b.dtype),  # e^b windows
        ]
        n_ids = 5 if mirror else 3  # seg/ik/ctr (+pij/pik under mirror)
        scratch_shapes = (
            [_id_scratch(chunk)] * n_ids
            + [pltpu.VMEM((2, chunk, dp), a.dtype)]       # a blocks
            + gather_scrs
            + [pltpu.SemaphoreType.DMA((2,))] * (n_ids + 4))
        kernel = _bond_conv_kernel_hbm
    else:
        whole = lambda x: pl.BlockSpec(x.shape, lambda i, *_: (0, 0))
        table_specs = ([whole(x) for x in ids]
                       + [whole(v), whole(e), e_tile_spec, whole(e_b),
                          eb_tile_spec, whole(a)])
        operands = (*ids, v, e, e, e_b, e_b, a)
        scratch_shapes = []
        kernel = _bond_conv_kernel
    kernel = functools.partial(
        kernel, block_rows=block_rows, chunk=chunk, d_real=d_real,
        gather_tile=gather_tile, mirror=mirror, n_chunks=seg.shape[0])
    # gather walks: 0 = e (and non-mirror e^b) via ik, 1 = v via ctr,
    # 2 = the Eu e^b mirror table via pij/pik
    win = _walk_windows([[ik], [ctr]] + ([[pij, pik]] if mirror else []),
                        gather_tile)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=grid,
        in_specs=table_specs + [_const_spec((dp, hp2))] * 4
        + [_const_spec((1, hp2))] * 3,
        out_specs=pl.BlockSpec((block_rows, hp), _row_block),
        scratch_shapes=scratch_shapes,
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b_rows, hp), jnp.float32),
        compiler_params=_compiler_params(),
        interpret=interpret,
    )(_tile_offsets(offsets, block_rows), win, *operands,
      w1, w2, w3, w4, b, ln_scale, ln_bias)


# ---------------------------------------------------------------------------
# direct-force readout megakernel: bonds -> atoms (Eq. 7)
# + optional bond-virial stress epilogue: bonds -> crystals (DESIGN.md §7)
# ---------------------------------------------------------------------------

def _bond_scalar_mlp(e_c, w1_ref, b1_ref, w2_ref, b2_ref):
    """(chunk, DP) bond features -> (chunk, 1) per-bond scalars n_ij."""
    h = jax.nn.silu(_mm(e_c, w1_ref[...])
                    + b1_ref[...].astype(jnp.float32))         # (chunk, DP)
    # n_ij is a SCALAR per bond (Eq. 8 equivariance proof): a lane
    # reduction instead of a 1-column matmul; f32 accumulation (§4)
    return jnp.sum(h * w2_ref[...].astype(jnp.float32), axis=-1,
                   keepdims=True) + b2_ref[0, 0].astype(jnp.float32)


def _virial_epilogue(sig_ref, cry, n, xh, base, start, end, chunk: int):
    """sig[c] += sum_{edges of this tile in crystal c} n d x_hat⊗x_hat.

    ``cry`` is the chunk's ``(1, chunk)`` crystal-id row; the bond
    distance d rides in x_hat's lane ``_DIST_LANE``.  The ownership mask is
    the force one-hot's [start, end) window, so nothing double-counts.
    Outer products are three MXU contractions per chunk — sig[:, m] +=
    onehot_c @ (x_hat ⊙ x_hat_m ⊙ n d) — so the (E, 3, 3) tensor never
    exists, not even tiled.  Lane ``_DIST_LANE`` of each accumulator block
    collects junk the wrapper slices off."""
    bp = sig_ref.shape[0]
    rows = jax.lax.broadcasted_iota(jnp.int32, (bp, chunk), 0)
    oh_c = ((cry == rows) & _edge_valid(base, start, end, chunk)
            ).astype(jnp.float32)                              # (Bp, chunk)
    w = n * xh[:, _DIST_LANE:_DIST_LANE + 1]                   # (chunk, 1)
    for m in range(3):
        sig_ref[:, m * 128:(m + 1) * 128] += _mm(
            oh_c, xh * (xh[:, m:m + 1] * w))


def _force_kernel(offs_ref, seg_ref, e_ref, xhat_ref, w1_ref, b1_ref,
                  w2_ref, b2_ref, out_ref, *, block_rows: int, chunk: int):
    i = pl.program_id(0)
    r0 = i * block_rows
    start = offs_ref[i]
    end = offs_ref[i + 1]
    out_ref[...] = jnp.zeros(out_ref.shape, out_ref.dtype)

    def body(k, carry):
        base = k * chunk
        oh_w = _window_onehot(_id_row(seg_ref, k), r0, start, end, base,
                              chunk, block_rows)
        e_c = e_ref[pl.ds(base, chunk), :]
        n = _bond_scalar_mlp(e_c, w1_ref, b1_ref, w2_ref, b2_ref)
        contrib = n * xhat_ref[pl.ds(base, chunk), :].astype(jnp.float32)
        out_ref[...] += _mm(oh_w, contrib).astype(out_ref.dtype)
        return carry

    jax.lax.fori_loop(start // chunk, pl.cdiv(end, chunk), body, 0)


def _force_virial_kernel(offs_ref, seg_ref, cry_ref, e_ref, xhat_ref,
                         w1_ref, b1_ref, w2_ref, b2_ref, out_ref, sig_ref,
                         *, block_rows: int, chunk: int):
    """Force readout + fused per-crystal virial epilogue (DESIGN.md §7).

    The force tile walk is identical to ``_force_kernel``; while n_ij and
    x_hat sit in registers, ``_virial_epilogue`` also accumulates into the
    SHARED (Bp, 3*128) accumulator block.  Its index_map is constant, so
    the block stays resident across the (sequential) grid and the
    per-program partials sum in place — the classic Pallas reduction
    pattern (init at program 0 via ``pl.when``).  Each real edge belongs
    to exactly one row tile (the same [start, end) CSR ownership as the
    force path), so nothing double-counts; the padded tail is past every
    row's end and never contributes.
    """
    i = pl.program_id(0)
    r0 = i * block_rows
    start = offs_ref[i]
    end = offs_ref[i + 1]
    out_ref[...] = jnp.zeros(out_ref.shape, out_ref.dtype)

    @pl.when(i == 0)
    def _init():
        sig_ref[...] = jnp.zeros(sig_ref.shape, sig_ref.dtype)

    def body(k, carry):
        base = k * chunk
        oh_w = _window_onehot(_id_row(seg_ref, k), r0, start, end, base,
                              chunk, block_rows)
        e_c = e_ref[pl.ds(base, chunk), :]
        n = _bond_scalar_mlp(e_c, w1_ref, b1_ref, w2_ref, b2_ref)
        xh = xhat_ref[pl.ds(base, chunk), :].astype(jnp.float32)
        out_ref[...] += _mm(oh_w, n * xh).astype(out_ref.dtype)
        _virial_epilogue(sig_ref, _id_row(cry_ref, k), n, xh, base, start,
                         end, chunk)
        return carry

    jax.lax.fori_loop(start // chunk, pl.cdiv(end, chunk), body, 0)


def _force_kernel_hbm(offs_ref, seg_ref, e_ref, xhat_ref, w1_ref, b1_ref,
                      w2_ref, b2_ref, out_ref, seg_scr, e_scr, xh_scr,
                      seg_sem, e_sem, xh_sem, *, block_rows: int,
                      chunk: int):
    """HBM-residency force readout (DESIGN.md §9): the bond payloads
    (``seg``, ``e``, ``x_hat``) stream in double-buffered chunk blocks."""
    i = pl.program_id(0)
    r0 = i * block_rows
    start = offs_ref[i]
    end = offs_ref[i + 1]
    out_ref[...] = jnp.zeros(out_ref.shape, out_ref.dtype)
    streams = ((seg_ref, seg_scr, seg_sem), (e_ref, e_scr, e_sem),
               (xhat_ref, xh_scr, xh_sem))

    def body(k, slot):
        oh_w = _window_onehot(seg_scr[slot], r0, start, end, k * chunk,
                              chunk, block_rows)
        n = _bond_scalar_mlp(e_scr[slot], w1_ref, b1_ref, w2_ref, b2_ref)
        contrib = n * xh_scr[slot].astype(jnp.float32)
        out_ref[...] += _mm(oh_w, contrib).astype(out_ref.dtype)

    _stream_loop(start // chunk, pl.cdiv(end, chunk), streams, body)


def _force_virial_kernel_hbm(offs_ref, seg_ref, cry_ref, e_ref, xhat_ref,
                             w1_ref, b1_ref, w2_ref, b2_ref, out_ref,
                             sig_ref, seg_scr, cry_scr, e_scr, xh_scr,
                             seg_sem, cry_sem, e_sem, xh_sem, *,
                             block_rows: int, chunk: int):
    """HBM-residency force + virial readout: the ``_force_virial_kernel``
    epilogue on streamed bond payloads (DESIGN.md §7/§9).  The virial
    accumulator keeps its constant-index-map VMEM residency — it is
    (Bp, 3*128), crystal-count sized, never the binding constraint."""
    i = pl.program_id(0)
    r0 = i * block_rows
    start = offs_ref[i]
    end = offs_ref[i + 1]
    out_ref[...] = jnp.zeros(out_ref.shape, out_ref.dtype)

    @pl.when(i == 0)
    def _init():
        sig_ref[...] = jnp.zeros(sig_ref.shape, sig_ref.dtype)

    streams = ((seg_ref, seg_scr, seg_sem), (cry_ref, cry_scr, cry_sem),
               (e_ref, e_scr, e_sem), (xhat_ref, xh_scr, xh_sem))

    def body(k, slot):
        base = k * chunk
        oh_w = _window_onehot(seg_scr[slot], r0, start, end, base, chunk,
                              block_rows)
        n = _bond_scalar_mlp(e_scr[slot], w1_ref, b1_ref, w2_ref, b2_ref)
        xh = xh_scr[slot].astype(jnp.float32)
        out_ref[...] += _mm(oh_w, n * xh).astype(out_ref.dtype)
        _virial_epilogue(sig_ref, cry_scr[slot], n, xh, base, start, end,
                         chunk)

    _stream_loop(start // chunk, pl.cdiv(end, chunk), streams, body)


def fused_force_readout_pallas(
    e: jnp.ndarray,        # (E, DP) f32 final bond features
    x_hat: jnp.ndarray,    # (E, XP) f32 unit bond vectors in lanes 0..2;
                           # lane _DIST_LANE holds the distance (virial),
                           # the rest is zero
    seg: jnp.ndarray,      # (E/chunk, chunk) int32 bond_center id rows,
                           # sorted over the real prefix
    offsets: jnp.ndarray,  # (A + 1,) int32 CSR row pointers
    w1: jnp.ndarray,       # (DP, DP)
    b1: jnp.ndarray,       # (1, DP)
    w2: jnp.ndarray,       # (1, DP) row vector (the (D, 1) head transposed)
    b2: jnp.ndarray,       # (1, XP) scalar bias broadcast, read at [0, 0]
    *,
    interpret: bool,
    cry: jnp.ndarray | None = None,   # (E/chunk, chunk) int32 bond_crystal
    num_crystals: int = 0,            # Bp, a block_rows multiple (virial)
    virial: bool = False,
    block_rows: int = 8,
    chunk: int = 256,
    residency: str = "vmem",
):
    """Fused Eq. 7 force readout; with ``virial=True`` the SAME launch also
    returns the (Bp, 3*128) per-crystal virial accumulator (lanes
    ``m*128 + n`` hold sum n d x_hat_m x_hat_n for n < 3; DESIGN.md §7)."""
    e_rows, dp = e.shape
    xp = x_hat.shape[1]
    a_rows = offsets.shape[0] - 1
    hbm = _check_residency(residency)
    assert e_rows % chunk == 0 and seg.shape == (e_rows // chunk, chunk), \
        (e_rows, seg.shape, chunk)
    assert a_rows % block_rows == 0, (a_rows, block_rows)
    grid = (a_rows // block_rows,)

    def _payload(x, block_rows_hbm):
        # hbm: DMA blocks of one id row or one chunk of payload rows
        if hbm:
            return _any_spec(), _blocks(x, block_rows_hbm)
        return pl.BlockSpec(x.shape, lambda i, *_: (0, 0)), x

    payloads = [_payload(seg, 1)]
    if virial:
        assert cry is not None
        assert num_crystals % block_rows == 0, (num_crystals, block_rows)
        payloads.append(_payload(cry, 1))
    payloads += [_payload(e, chunk), _payload(x_hat, chunk)]
    in_specs = [spec for spec, _ in payloads] + [
        pl.BlockSpec((dp, dp), lambda i, *_: (0, 0)),
        pl.BlockSpec((1, dp), lambda i, *_: (0, 0)),
        pl.BlockSpec((1, dp), lambda i, *_: (0, 0)),
        pl.BlockSpec((1, xp), lambda i, *_: (0, 0)),
    ]
    operands = [_tile_offsets(offsets, block_rows)] \
        + [x for _, x in payloads] + [w1, b1, w2, b2]
    out_specs = pl.BlockSpec((block_rows, xp), lambda i, *_: (i, 0))
    out_shape = jax.ShapeDtypeStruct((a_rows, xp), jnp.float32)
    scratch_shapes = []
    if hbm:
        n_ids = 2 if virial else 1               # seg (+ cry)
        scratch_shapes = (
            [_id_scratch(chunk)] * n_ids
            + [pltpu.VMEM((2, chunk, dp), e.dtype),      # e blocks
               pltpu.VMEM((2, chunk, xp), x_hat.dtype)]  # x_hat blocks
            + [pltpu.SemaphoreType.DMA((2,))] * (n_ids + 2))
    if virial:
        # constant index_map: one VMEM-resident accumulator block shared
        # by every grid step (sequential on TPU -> race-free reduction)
        out_specs = (out_specs,
                     pl.BlockSpec((num_crystals, 3 * 128),
                                  lambda i, *_: (0, 0)))
        out_shape = (out_shape,
                     jax.ShapeDtypeStruct((num_crystals, 3 * 128),
                                          jnp.float32))
        body = _force_virial_kernel_hbm if hbm else _force_virial_kernel
    else:
        body = _force_kernel_hbm if hbm else _force_kernel
    kernel = functools.partial(body, block_rows=block_rows, chunk=chunk)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=scratch_shapes,
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=out_shape,
        compiler_params=_compiler_params(),
        interpret=interpret,
    )(*operands)


# ---------------------------------------------------------------------------
# symmetric-trunk bond_conv megakernel pair (DESIGN.md §10):
#   phase A — one gated-MLP message per dedup angle (Au rows)
#   phase B — destination-tiled accumulation into Eu bond rows through the
#             sym-incidence store (each real Au message lands on BOTH
#             undirected bonds of its pair)
# Splitting at the Au->Eu scatter is what realizes the FLOP halving: a
# single destination-tiled kernel would recompute phi once per incidence
# (twice per angle), giving back most of the savings.  The (Au, HP) f32
# message buffer between the launches is the price — half the size of the
# directed angle table it replaces.
# ---------------------------------------------------------------------------

def _sym_msg_kernel(win_ref, ctr_ref, du1_ref, du2_ref, v_ref, e_ref,
                    eb_ref, a_ref, w1_ref, w23_ref, w4_ref, b_ref, lns_ref,
                    lnb_ref, out_ref, *, d_real: int, gather_tile: int,
                    n_chunks: int):
    """Phase A: msg[w] = phi([v[ctr], e_s, e_s, a_u]) * e_b[du1] * e_b[du2]
    with e_s = e[du1] + e[du2].  The swap-symmetric e_s feeds both e slots
    of the directed bond MLP, so the w2/w3 GEMMs collapse into one GEMM
    against the precombined w23 = w2 + w3.  Padded Au rows produce finite
    garbage that phase B's CSR ownership never references."""
    hp = b_ref.shape[-1] // 2
    i = pl.program_id(0)
    (v_c,) = _gather_rows(ctr_ref[...], (v_ref,), gather_tile,
                          _walk_bounds(win_ref, 0, i, n_chunks))
    e_win = _walk_bounds(win_ref, 1, i, n_chunks)
    e1, eb1 = _gather_rows(du1_ref[...], (e_ref, eb_ref), gather_tile, e_win)
    e2, eb2 = _gather_rows(du2_ref[...], (e_ref, eb_ref), gather_tile, e_win)
    a_c = a_ref[...]
    y = _mm(v_c, w1_ref[...]) + _mm(e1 + e2, w23_ref[...]) \
        + _mm(a_c, w4_ref[...]) + b_ref[...].astype(jnp.float32)
    msg = _gated_epilogue(y, lns_ref, lnb_ref, hp, d_real)
    out_ref[...] = (msg * eb1 * eb2).astype(out_ref.dtype)


def _sym_msg_kernel_hbm(win_ref, ctr_ref, du1_ref, du2_ref, v_ref, e_ref,
                        eb_ref, a_ref, w1_ref, w23_ref, w4_ref, b_ref,
                        lns_ref, lnb_ref, out_ref, v_gscr, e_gscr, eb_gscr,
                        v_gsem, e_gsem, eb_gsem, *, d_real: int,
                        gather_tile: int, n_chunks: int):
    """HBM-residency phase A: the v/e/e^b tables stay in HBM and stream in
    gather_tile windows; both du gathers share one walk of (e, e^b).  The
    Au-blocked id rows and a_u remain VMEM block operands."""
    hp = b_ref.shape[-1] // 2
    i = pl.program_id(0)
    ((v_c,),) = _gather_rows_hbm(
        (ctr_ref[...],), ((v_ref, v_gscr, v_gsem),), gather_tile,
        _walk_bounds(win_ref, 0, i, n_chunks))
    ((e1, eb1), (e2, eb2)) = _gather_rows_hbm(
        (du1_ref[...], du2_ref[...]),
        ((e_ref, e_gscr, e_gsem), (eb_ref, eb_gscr, eb_gsem)), gather_tile,
        _walk_bounds(win_ref, 1, i, n_chunks))
    a_c = a_ref[...]
    y = _mm(v_c, w1_ref[...]) + _mm(e1 + e2, w23_ref[...]) \
        + _mm(a_c, w4_ref[...]) + b_ref[...].astype(jnp.float32)
    msg = _gated_epilogue(y, lns_ref, lnb_ref, hp, d_real)
    out_ref[...] = (msg * eb1 * eb2).astype(out_ref.dtype)


def fused_sym_msg_pallas(
    v: jnp.ndarray,        # (A, DP) f32 atom features
    e: jnp.ndarray,        # (EU, DP) f32 undirected bond table
    a_u: jnp.ndarray,      # (UA, DP) f32 dedup angle features
    e_b: jnp.ndarray,      # (EU, HP) undirected bond envelope table
    ctr: jnp.ndarray,      # (UA/msg_block, msg_block) int32 id rows of
                           # bond_center[und_angle_ij]
    du1: jnp.ndarray,      # (UA/msg_block, msg_block) bond_pair[und_angle_ij]
    du2: jnp.ndarray,      # (UA/msg_block, msg_block) bond_pair[und_angle_ik]
    w1: jnp.ndarray, w23: jnp.ndarray, w4: jnp.ndarray,  # (DP, 2*HP) each
    b: jnp.ndarray,        # (1, 2*HP)
    ln_scale: jnp.ndarray, ln_bias: jnp.ndarray,         # (1, 2*HP)
    *,
    d_real: int,
    interpret: bool,
    msg_block: int = 256,
    gather_tile: int = 256,
    residency: str = "vmem",
) -> jnp.ndarray:
    a_rows, dp = v.shape
    eu_rows = e.shape[0]
    ua_rows = a_u.shape[0]
    hp2 = b.shape[-1]
    hp = hp2 // 2
    hbm = _check_residency(residency)
    assert ua_rows % msg_block == 0, (ua_rows, msg_block)
    assert ctr.shape == (ua_rows // msg_block, msg_block), \
        (ctr.shape, ua_rows, msg_block)
    assert a_rows % gather_tile == 0, (a_rows, gather_tile)
    assert eu_rows % gather_tile == 0, (eu_rows, gather_tile)
    assert e_b.shape[0] == eu_rows, (e_b.shape, eu_rows)
    grid = (ua_rows // msg_block,)
    # one (1, msg_block) id row per grid step: the leading block axis is
    # squeezed, so the row keeps the full lane-dense trailing dims
    id_spec = pl.BlockSpec((None, 1, msg_block), lambda i, win: (i, 0, 0))
    ids = [_blocks(x, 1) for x in (ctr, du1, du2)]
    if hbm:
        table_specs = [_any_spec(), _any_spec(), _any_spec()]
        tables = (_blocks(v, gather_tile), _blocks(e, gather_tile),
                  _blocks(e_b, gather_tile))
        scratch_shapes = [
            pltpu.VMEM((2, gather_tile, dp), v.dtype),    # v windows
            pltpu.VMEM((2, gather_tile, dp), e.dtype),    # e windows
            pltpu.VMEM((2, gather_tile, hp), e_b.dtype),  # e^b windows
        ] + [pltpu.SemaphoreType.DMA((2,))] * 3
        kernel = _sym_msg_kernel_hbm
    else:
        table_specs = [_const_spec((a_rows, dp)), _const_spec((eu_rows, dp)),
                       _const_spec((eu_rows, hp))]
        tables = (v, e, e_b)
        scratch_shapes = []
        kernel = _sym_msg_kernel
    kernel = functools.partial(kernel, d_real=d_real,
                               gather_tile=gather_tile,
                               n_chunks=ctr.shape[0])
    # gather walks: 0 = v via ctr, 1 = (e, e^b) via du1/du2
    win = _walk_windows([[ctr], [du1, du2]], gather_tile)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[id_spec, id_spec, id_spec] + table_specs + [
            pl.BlockSpec((msg_block, dp), _row_block),  # a_u blocks
        ] + [_const_spec((dp, hp2))] * 3 + [_const_spec((1, hp2))] * 3,
        out_specs=pl.BlockSpec((msg_block, hp), _row_block),
        scratch_shapes=scratch_shapes,
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((ua_rows, hp), jnp.float32),
        compiler_params=_compiler_params(),
        interpret=interpret,
    )(win, *ids, *tables, a_u, w1, w23, w4, b, ln_scale, ln_bias)


def _sym_accum_kernel(offs_ref, win_ref, dest_ref, rep_ref, msg_ref,
                      out_ref, *, block_rows: int, chunk: int,
                      gather_tile: int, n_chunks: int):
    """Phase B: agg[u] = sum over this block's CSR incidence range of
    msg[rep] — the same destination-tiled window-one-hot walk as every
    other aggregation kernel, with the message rows gathered through the
    duplicate-pointer ``rep`` map."""
    i = pl.program_id(0)
    r0 = i * block_rows
    start = offs_ref[i]
    end = offs_ref[i + 1]
    out_ref[...] = jnp.zeros(out_ref.shape, out_ref.dtype)

    def body(k, carry):
        oh_w = _window_onehot(_id_row(dest_ref, k), r0, start, end,
                              k * chunk, chunk, block_rows)
        (m_c,) = _gather_rows(_id_row(rep_ref, k), (msg_ref,), gather_tile,
                              _walk_bounds(win_ref, 0, k, n_chunks))
        out_ref[...] += _mm(oh_w, m_c).astype(out_ref.dtype)
        return carry

    jax.lax.fori_loop(start // chunk, pl.cdiv(end, chunk), body, 0)


def _sym_accum_kernel_hbm(offs_ref, win_ref, dest_ref, rep_ref, msg_ref,
                          out_ref, dest_scr, rep_scr, m_gscr, dest_sem,
                          rep_sem, m_gsem, *, block_rows: int, chunk: int,
                          gather_tile: int, n_chunks: int):
    """HBM-residency phase B: dest/rep id rows stream in chunk blocks; the
    (Au, HP) message buffer stays in HBM and is walked in gather_tile
    windows."""
    i = pl.program_id(0)
    r0 = i * block_rows
    start = offs_ref[i]
    end = offs_ref[i + 1]
    out_ref[...] = jnp.zeros(out_ref.shape, out_ref.dtype)
    edge_streams = ((dest_ref, dest_scr, dest_sem),
                    (rep_ref, rep_scr, rep_sem))

    def body(k, slot):
        oh_w = _window_onehot(dest_scr[slot], r0, start, end, k * chunk,
                              chunk, block_rows)
        ((m_c,),) = _gather_rows_hbm(
            (rep_scr[slot],), ((msg_ref, m_gscr, m_gsem),), gather_tile,
            _walk_bounds(win_ref, 0, k, n_chunks))
        out_ref[...] += _mm(oh_w, m_c).astype(out_ref.dtype)

    _stream_loop(start // chunk, pl.cdiv(end, chunk), edge_streams, body)


def fused_sym_accum_pallas(
    msg: jnp.ndarray,      # (UA, HP) f32 phase-A messages
    dest: jnp.ndarray,     # (IC/chunk, chunk) int32 sym_dest id rows,
                           # sorted over the real prefix
    rep: jnp.ndarray,      # (IC/chunk, chunk) int32 sym_rep id rows
    offsets: jnp.ndarray,  # (EU + 1,) int32 CSR incidence row pointers
    *,
    eu_rows: int,
    interpret: bool,
    block_rows: int = 8,
    chunk: int = 256,
    gather_tile: int = 256,
    residency: str = "vmem",
) -> jnp.ndarray:
    ua_rows, hp = msg.shape
    hbm = _check_residency(residency)
    assert dest.shape[1] == chunk, (dest.shape, chunk)
    assert eu_rows % block_rows == 0, (eu_rows, block_rows)
    assert ua_rows % gather_tile == 0, (ua_rows, gather_tile)
    assert offsets.shape[0] == eu_rows + 1, (offsets.shape, eu_rows)
    grid = (eu_rows // block_rows,)
    if hbm:
        in_specs = [_any_spec(), _any_spec(), _any_spec()]
        operands = (_blocks(dest, 1), _blocks(rep, 1),
                    _blocks(msg, gather_tile))
        scratch_shapes = [
            _id_scratch(chunk),                           # dest
            _id_scratch(chunk),                           # rep
            pltpu.VMEM((2, gather_tile, hp), msg.dtype),  # msg windows
        ] + [pltpu.SemaphoreType.DMA((2,))] * 3
        kernel = _sym_accum_kernel_hbm
    else:
        in_specs = [
            pl.BlockSpec(dest.shape, lambda i, *_: (0, 0)),
            pl.BlockSpec(rep.shape, lambda i, *_: (0, 0)),
            pl.BlockSpec((ua_rows, hp), lambda i, *_: (0, 0)),
        ]
        operands = (dest, rep, msg)
        scratch_shapes = []
        kernel = _sym_accum_kernel
    kernel = functools.partial(kernel, block_rows=block_rows, chunk=chunk,
                               gather_tile=gather_tile,
                               n_chunks=dest.shape[0])
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((block_rows, hp), lambda i, *_: (i, 0)),
        scratch_shapes=scratch_shapes,
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((eu_rows, hp), jnp.float32),
        compiler_params=_compiler_params(),
        interpret=interpret,
    )(_tile_offsets(offsets, block_rows), _walk_windows([[rep]], gather_tile),
      *operands)

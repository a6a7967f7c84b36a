"""Jit'd public wrappers around the Pallas kernels.

Responsibilities:
  - pad inputs to kernel-aligned shapes (rows -> block multiple, basis
    lanes -> 128) and slice the outputs back;
  - select interpret mode automatically (interpret=True off-TPU so the
    same code paths run in CI; compiled Mosaic on TPU);
  - expose the packed-parameter calling convention used by
    repro.core.interaction.gated_mlp_apply(impl="pallas");
  - preserve operand dtypes (DESIGN.md §4): bf16 inputs reach the kernels
    as bf16 VMEM tiles (the kernels accumulate f32 in-register) and the
    sliced outputs are cast back to the operand dtype.  The custom-VJP
    backwards below upcast their recompute to f32 and accumulate
    cotangents in f32 regardless of the operand dtype.

Every op here is differentiable: the basis kernels (fused_rbf /
fused_fourier), the GatedMLP, and the message-passing megakernels all
carry chunked recompute custom VJPs (the DESIGN.md §3 pattern), so
``mlp_impl="pallas"`` trains end to end — the seed-era forward-only
caveat is gone.  The conv wrappers additionally accept the DESIGN.md §5
``pair`` mirror maps for the undirected bond store.
"""
from __future__ import annotations

import functools
import math
import os

import jax
import jax.numpy as jnp
import numpy as np

from . import ref
from .flash_attention import flash_attention_pallas
from .fused_fourier import fused_fourier_pallas
from .fused_gated_mlp import fused_gated_mlp_pallas
from .fused_message_passing import (
    _DIST_LANE,
    fused_atom_conv_pallas,
    fused_bond_conv_pallas,
    fused_force_readout_pallas,
    fused_sym_accum_pallas,
    fused_sym_msg_pallas,
)
from .fused_rbf import fused_rbf_pallas
from .fused_segment_sum import fused_segment_sum_pallas
from .fused_swiglu import fused_swiglu_pallas


@functools.cache
def _interpret() -> bool:
    # REPRO_KERNELS_INTERPRET=1 forces interpret mode regardless of backend
    # (CI sets it so the kernel paths are exercised without a TPU).
    if os.environ.get("REPRO_KERNELS_INTERPRET", "") not in ("", "0"):
        return True
    return jax.default_backend() != "tpu"


# ---------------------------------------------------------------------------
# Table residency (DESIGN.md §9): "vmem" | "hbm" | "auto"
# ---------------------------------------------------------------------------
#
# Under "vmem" the megakernels keep their operand tables whole-array
# VMEM-resident; "hbm" leaves them in HBM and streams double-buffered DMA
# slices/windows through ping/pong scratch.  "auto" (the config default)
# resolves per launch from the padded operand-table bytes vs the VMEM
# budget, so CI-small shapes keep the exact vmem lowering while oversized
# batches transparently stream.

_VMEM_BUDGET_ENV = "REPRO_VMEM_BUDGET_MB"
_DEFAULT_VMEM_BUDGET_MB = 16.0

RESIDENCY_TIERS = ("vmem", "hbm")


def vmem_budget_bytes() -> int:
    """Byte budget the "auto" residency heuristic compares operand-table
    bytes against (DESIGN.md §9).  Default ~16 MiB (a TPU core's VMEM);
    override with REPRO_VMEM_BUDGET_MB (tests set it tiny to force the
    hbm tier on small shapes)."""
    return int(float(os.environ.get(_VMEM_BUDGET_ENV,
                                    _DEFAULT_VMEM_BUDGET_MB)) * 2 ** 20)


def _resolve_residency(residency: str, table_bytes: int) -> str:
    if residency == "auto":
        return "vmem" if table_bytes <= vmem_budget_bytes() else "hbm"
    if residency not in RESIDENCY_TIERS:
        raise ValueError(
            f"table_residency must be 'auto', 'vmem' or 'hbm', "
            f"got {residency!r}")
    return residency


def _itemsize(dtype) -> int:
    return np.dtype(dtype).itemsize


def estimate_table_bytes(num_atoms: int, num_bonds: int, num_angles: int,
                         dim: int, *, num_und: int | None = None,
                         itemsize: int = 4) -> int:
    """Operand-table bytes the §3 megakernels keep VMEM-resident under
    ``table_residency="vmem"`` — the max over the atom_conv / bond_conv /
    force-readout launches, mirroring the ops wrappers' padding math and
    the layout the chip gives each operand: feature tables at their
    128-lane padded width, id streams as lane-dense ``(rows / chunk,
    chunk)`` int32 rows (``_id_bytes``).  Model-level twin of the
    per-launch resolution inside each op: serve admission, the
    bench_iteration residency bar, and the oversized-structure tests use
    it to decide whether a batch is VMEM-feasible without tracing a
    kernel.

    ``num_und``: Eu rows of the §5 mirror tables (``bond_store=
    "undirected"``); None means the directed store.
    """
    dp = _round_up(max(dim, 1), _LANE)
    hp = dp
    mirror = num_und is not None
    chunk = 256  # the conv/readout wrappers' edge chunk
    # atom_conv: ids (seg/nbr/pair) + v table + e payload + e^a
    ep = _round_up(max(num_bonds, 1), chunk)
    ap = _round_up(max(num_atoms, 1), math.lcm(8, 256))
    ea_rows = _round_up(max(num_und, 1), 256) if mirror else ep
    atom = (3 * _id_bytes(ep, chunk) + ap * dp * itemsize
            + ep * dp * itemsize + ea_rows * hp * itemsize)
    # bond_conv: ids (seg/ik/ctr/pij/pik) + v/e tables + a payload + e^b
    epa = _round_up(max(num_angles, 1), chunk)
    bp = _round_up(max(num_bonds, 1), math.lcm(32, 512))
    apg = _round_up(max(num_atoms, 1), 512)
    eb_rows = _round_up(max(num_und, 1), 512) if mirror else bp
    bond = (5 * _id_bytes(epa, chunk) + apg * dp * itemsize
            + bp * dp * itemsize + epa * dp * itemsize
            + eb_rows * hp * itemsize)
    # force readout: ids (seg + virial cry) + e + x_hat
    force = 2 * _id_bytes(ep, chunk) + ep * dp * itemsize \
        + ep * _LANE * itemsize
    return max(atom, bond, force)


def resident_vmem_estimate(residency: str, num_atoms: int, num_bonds: int,
                           num_angles: int, dim: int, *,
                           num_und: int | None = None,
                           itemsize: int = 4, chunk: int = 256,
                           gather_tile: int = 512) -> int:
    """Deterministic resident-VMEM estimate per residency tier: the vmem
    tier holds the full operand tables (``estimate_table_bytes``); the hbm
    tier holds only the ping/pong scratch — 2 slots x (one id block per id
    stream, tiled to 8 sublanes, + chunk rows per payload stream +
    gather_tile rows per table walk).  Backend-independent, so the
    bench_iteration residency bar can be ENFORCED in interpret mode."""
    if residency == "vmem":
        return estimate_table_bytes(num_atoms, num_bonds, num_angles, dim,
                                    num_und=num_und, itemsize=itemsize)
    dp = _round_up(max(dim, 1), _LANE)
    # worst launch is bond_conv: 5 id streams + the angle payload + 3
    # gather-table walks
    edge = 2 * (5 * 8 * chunk * 4 + chunk * dp * itemsize)
    gather = 2 * gather_tile * 3 * dp * itemsize
    return edge + gather


def _pad_rows(x: jnp.ndarray, mult: int) -> tuple[jnp.ndarray, int]:
    n = x.shape[0]
    pad = (-n) % mult
    if pad:
        x = jnp.pad(x, [(0, pad)] + [(0, 0)] * (x.ndim - 1))
    return x, n


# ---------------------------------------------------------------------------
# Basis + GatedMLP kernels with chunked recompute backwards
# ---------------------------------------------------------------------------
#
# These three ops were forward-only in the seed (no VJP on a pallas_call),
# which pinned mlp_impl="pallas" to inference.  Each now carries a custom
# VJP in the §3 recompute style: the forward saves only its (tiny) primal
# operands, and the backward re-derives the basis/MLP chunk-by-chunk with
# a chunk-local jax.vjp of the analytic reference math (kernels/ref.py) —
# f32 accumulation, one (chunk, K) transient tile, nothing stored across
# forward/backward.

def _row_chunks(n_padded: int, chunk: int):
    return n_padded // chunk


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def _fused_rbf(dist, freqs, r_cut, p, block_m):
    k = freqs.shape[0]
    k_pad = (-k) % 128
    freqs_p = jnp.pad(freqs, (0, k_pad)) if k_pad else freqs
    dist_p, n = _pad_rows(dist, block_m)
    out = fused_rbf_pallas(
        dist_p, freqs_p, r_cut, p, block_m=block_m, interpret=_interpret()
    )
    return out[:n, :k]


def _fused_rbf_fwd(dist, freqs, r_cut, p, block_m):
    return _fused_rbf(dist, freqs, r_cut, p, block_m), (dist, freqs)


def _fused_rbf_bwd(r_cut, p, block_m, res, g):
    """Chunked analytic backward: d(sRBF)/d(dist, freqs) via a per-chunk
    jax.vjp of the reference basis (no saved intermediates)."""
    dist, freqs = res
    n = dist.shape[0]
    np_rows = _round_up(max(n, 1), block_m)
    dist_p = jnp.pad(dist.astype(jnp.float32), (0, np_rows - n))
    # padded rows carry zero cotangents, so they contribute nothing
    g_p = jnp.pad(g.astype(jnp.float32),
                  ((0, np_rows - n), (0, 0)))
    freqs32 = freqs.astype(jnp.float32)

    def body(i, carry):
        dd, df = carry
        i0 = i * block_m
        dist_c = jax.lax.dynamic_slice(dist_p, (i0,), (block_m,))
        g_c = jax.lax.dynamic_slice(g_p, (i0, 0), (block_m, g_p.shape[1]))
        _, vjp = jax.vjp(
            lambda dc, fr: ref.fused_rbf_ref(dc, fr, r_cut, p),
            dist_c, freqs32)
        dd_c, df_c = vjp(g_c)
        return (jax.lax.dynamic_update_slice(dd, dd_c, (i0,)), df + df_c)

    dd, df = jax.lax.fori_loop(
        0, _row_chunks(np_rows, block_m), body,
        (jnp.zeros_like(dist_p), jnp.zeros_like(freqs32)))
    return dd[:n].astype(dist.dtype), df.astype(freqs.dtype)


_fused_rbf.defvjp(_fused_rbf_fwd, _fused_rbf_bwd)


def fused_rbf(dist, freqs, r_cut: float, p: int = 8, *, block_m: int = 512):
    """(N,) x (K,) -> (N, K) fused smooth-RBF basis.

    Differentiable w.r.t. distances AND the trainable frequencies (chunked
    recompute custom VJP — the forces/stress autodiff readout and training
    with ``mlp_impl="pallas"`` both pass through here).
    """
    return _fused_rbf(dist, freqs, r_cut, p, block_m)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2))
def _fused_fourier(theta, num_basis, block_m):
    theta_p, n = _pad_rows(theta, block_m)
    out = fused_fourier_pallas(
        theta_p, num_basis, block_m=block_m, interpret=_interpret()
    )
    return out[:n, :num_basis]


def _fused_fourier_fwd(theta, num_basis, block_m):
    return _fused_fourier(theta, num_basis, block_m), theta


def _fused_fourier_bwd(num_basis, block_m, theta, g):
    """Chunked analytic backward: d(FT)/d(theta) per chunk."""
    n = theta.shape[0]
    np_rows = _round_up(max(n, 1), block_m)
    theta_p = jnp.pad(theta.astype(jnp.float32), (0, np_rows - n))
    g_p = jnp.pad(g.astype(jnp.float32), ((0, np_rows - n), (0, 0)))

    def body(i, dt):
        i0 = i * block_m
        theta_c = jax.lax.dynamic_slice(theta_p, (i0,), (block_m,))
        g_c = jax.lax.dynamic_slice(g_p, (i0, 0), (block_m, g_p.shape[1]))
        _, vjp = jax.vjp(
            lambda tc: ref.fused_fourier_ref(tc, num_basis), theta_c)
        (dt_c,) = vjp(g_c)
        return jax.lax.dynamic_update_slice(dt, dt_c, (i0,))

    dt = jax.lax.fori_loop(0, _row_chunks(np_rows, block_m), body,
                           jnp.zeros_like(theta_p))
    return (dt[:n].astype(theta.dtype),)


_fused_fourier.defvjp(_fused_fourier_fwd, _fused_fourier_bwd)


def fused_fourier(theta, num_basis: int, *, block_m: int = 512):
    """(N,) -> (N, num_basis) fused Fourier angle basis (differentiable
    w.r.t. theta via a chunked recompute custom VJP)."""
    return _fused_fourier(theta, num_basis, block_m)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _fused_gated_mlp_packed(x, w, b, ln_scale, ln_bias, block_m):
    x_p, m = _pad_rows(x, block_m)
    # GEMM operands share x's dtype (cast-to-compute view, DESIGN.md §4);
    # LN params stay as given — the kernel evaluates LN in f32 regardless
    out = fused_gated_mlp_pallas(
        x_p, w.astype(x.dtype), b.astype(x.dtype), ln_scale, ln_bias,
        block_m=block_m, interpret=_interpret(),
    )
    return out[:m]


def _fused_gated_mlp_packed_fwd(x, w, b, ln_scale, ln_bias, block_m):
    out = _fused_gated_mlp_packed(x, w, b, ln_scale, ln_bias, block_m)
    return out, (x, w, b, ln_scale, ln_bias)


def _fused_gated_mlp_packed_bwd(block_m, res, g):
    """Chunked recompute backward over row chunks of x (the §3 pattern):
    each iteration re-derives its (chunk, 2D) GatedMLP with a chunk-local
    jax.vjp of the packed reference — no LN statistics or activations are
    saved anywhere."""
    x, w, b, ln_scale, ln_bias = res
    m = x.shape[0]
    mp = _round_up(max(m, 1), block_m)
    x_p = _pad_rows_f32(x, mp)
    g_p = _pad_rows_f32(g, mp)
    f32 = lambda t: t.astype(jnp.float32)
    w32, b32, s32, o32 = f32(w), f32(b), f32(ln_scale), f32(ln_bias)

    def body(i, carry):
        dx, dw, db, ds, do = carry
        i0 = i * block_m
        x_c = _chunk_of(x_p, i0, block_m)
        g_c = _chunk_of(g_p, i0, block_m)
        _, vjp = jax.vjp(ref.gated_mlp_packed_ref, x_c, w32, b32, s32, o32)
        dx_c, dw_c, db_c, ds_c, do_c = vjp(g_c)
        return (jax.lax.dynamic_update_slice(dx, dx_c, (i0, 0)),
                dw + dw_c, db + db_c, ds + ds_c, do + do_c)

    init = (jnp.zeros_like(x_p), jnp.zeros_like(w32), jnp.zeros_like(b32),
            jnp.zeros_like(s32), jnp.zeros_like(o32))
    dx, dw, db, ds, do = jax.lax.fori_loop(
        0, _row_chunks(mp, block_m), body, init)
    return (dx[:m].astype(x.dtype), dw.astype(w.dtype), db.astype(b.dtype),
            ds.astype(ln_scale.dtype), do.astype(ln_bias.dtype))


_fused_gated_mlp_packed.defvjp(_fused_gated_mlp_packed_fwd,
                               _fused_gated_mlp_packed_bwd)


def fused_gated_mlp_packed(x, w, b, ln_scale, ln_bias, *, block_m: int = 256):
    """CHGNet GatedMLP from pre-packed parameters (w = [Wc ‖ Wg], packed
    once at init — repro.core.interaction.gated_mlp_init); no per-step
    parameter concat inside the jitted step.  Differentiable via a chunked
    recompute custom VJP, so ``mlp_impl="pallas"`` trains end to end."""
    return _fused_gated_mlp_packed(x, w, b, ln_scale, ln_bias, block_m)


def fused_gated_mlp(x, wc, bc, wg, bg, sc, oc, sg, og, *, block_m: int = 256):
    """CHGNet GatedMLP from separate core/gate weights (legacy calling
    convention; packs on the fly — prefer ``fused_gated_mlp_packed``)."""
    return fused_gated_mlp_packed(
        x,
        jnp.concatenate([wc, wg], axis=1),
        jnp.concatenate([bc, bg], axis=0),
        jnp.concatenate([sc, sg], axis=0),
        jnp.concatenate([oc, og], axis=0),
        block_m=block_m,
    )


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _fused_segment_sum(values, segment_ids, offsets, num_segments,
                       block_rows, chunk, residency):
    e, d = values.shape
    ep = _round_up(e, chunk)
    dp = _round_up(d, 128)
    sp = _round_up(num_segments, block_rows)
    values_p = jnp.pad(values, ((0, ep - e), (0, dp - d)))
    seg_p = _pad_ids(segment_ids, ep, chunk)
    offs_p = _pad_offsets(offsets, sp)
    # auto resolves from the padded operand bytes (pure function of static
    # shapes, so forward and grad-of-forward pick the same tier)
    residency = _resolve_residency(
        residency, _id_bytes(ep, chunk) + ep * dp * _itemsize(values.dtype))
    out = fused_segment_sum_pallas(
        values_p, seg_p, offs_p,
        block_rows=block_rows, chunk=chunk, residency=residency,
        interpret=_interpret(),
    )
    return out[:num_segments, :d].astype(values.dtype)


def _fused_segment_sum_fwd(values, segment_ids, offsets, num_segments,
                           block_rows, chunk, residency):
    out = _fused_segment_sum(values, segment_ids, offsets, num_segments,
                             block_rows, chunk, residency)
    return out, (segment_ids, offsets)


def _fused_segment_sum_bwd(num_segments, block_rows, chunk, residency,
                           res, g):
    # d/dv[e] of sum-into-rows is a gather: g[seg[e]] on real edges, 0 on
    # the padded tail — no scatter in the backward pass either.
    segment_ids, offsets = res
    valid = jnp.arange(segment_ids.shape[0]) < offsets[num_segments]
    dv = jnp.where(valid[:, None], g[segment_ids], 0.0).astype(g.dtype)
    f0 = jax.dtypes.float0  # integer primals take symbolic-zero cotangents
    return (dv, np.zeros(segment_ids.shape, f0), np.zeros(offsets.shape, f0))


_fused_segment_sum.defvjp(_fused_segment_sum_fwd, _fused_segment_sum_bwd)


def fused_segment_sum(values, segment_ids, offsets, num_segments: int,
                      *, block_rows: int = 8, chunk: int = 256,
                      table_residency: str = "auto"):
    """Sorted-segment reduction: (E, D) edges -> (num_segments, D) rows.

    Requires the sorted-segment layout (DESIGN.md §1): real edges sorted by
    ``segment_ids`` with CSR ``offsets`` of shape (num_segments + 1,),
    ``offsets[-1]`` == number of real edges.  Pads edges to a ``chunk``
    multiple, lanes to 128, and rows to a ``block_rows`` multiple, then
    slices back.  Differentiable (custom VJP: the backward is a gather).

    ``table_residency`` (DESIGN.md §9): "vmem" keeps values/ids whole-array
    resident, "hbm" streams them with double-buffered DMA, "auto" picks by
    operand bytes vs the VMEM budget.
    """
    return _fused_segment_sum(values, segment_ids, offsets, num_segments,
                              block_rows, chunk, table_residency)


# ---------------------------------------------------------------------------
# Fused message passing (gather -> GatedMLP -> envelope -> reduce, DESIGN §3)
# ---------------------------------------------------------------------------
#
# The forward runs the megakernels in fused_message_passing.py: no (E, kD)
# concat and no (E, D) message tensor ever reaches HBM.  The custom VJPs
# implement the redundancy bypass on the backward side: the forward saves
# ONLY the operands (which are live layer inputs anyway), and the backward
# recomputes the message path chunk-by-chunk inside a fori_loop — a
# per-chunk jax.vjp whose transient working set is one (chunk, kD) tile,
# never the full edge set.  Message activations therefore exist nowhere:
# not in the forward, not across forward/backward, and not whole-array in
# the backward.

_LANE = 128  # TPU lane width: feature dims and packed halves pad to this


def _pad2(x, rows, cols):
    # dtype-preserving: bf16 operands stay bf16 VMEM tiles (DESIGN.md §4)
    return jnp.pad(x, ((0, rows - x.shape[0]), (0, cols - x.shape[1])))


def _round_up(n: int, m: int) -> int:
    return n + (-n) % m


def _pad_rows_i32(x, rows):
    return jnp.pad(x.astype(jnp.int32), (0, rows - x.shape[0]))


def _pad_rows_f32(x, rows):
    return jnp.pad(x.astype(jnp.float32), ((0, rows - x.shape[0]), (0, 0)))


def _chunk_of(x, i0, chunk: int):
    if x.ndim == 1:
        return jax.lax.dynamic_slice(x, (i0,), (chunk,))
    return jax.lax.dynamic_slice(x, (i0, 0), (chunk, x.shape[1]))


def _pad_ids(ids, rows, width):
    """(n,) ids -> lane-dense ``(rows // width, width)`` int32 id rows,
    one kernel chunk per row (the layout every megakernel streams)."""
    return _pad_rows_i32(ids, rows).reshape(rows // width, width)


def _id_bytes(rows: int, width: int) -> int:
    """Bytes one padded id stream takes in VMEM: ``(rows // width,
    width)`` int32 rows, the row count tiled to 8 sublanes."""
    return _round_up(rows // width, 8) * width * 4


def _pack_lanes_vec(vec, d, hp):
    """(2d,) packed [core ‖ gate] -> (1, 2*hp) with halves lane-padded
    (dtype-preserving)."""
    out = jnp.zeros((2 * hp,), vec.dtype)
    out = out.at[:d].set(vec[:d])
    out = out.at[hp:hp + d].set(vec[d:])
    return out[None, :]


def _pack_lanes_w(wk, dp, d, hp):
    """(d_in_k, 2d) weight block -> (dp, 2*hp) with halves lane-padded
    (dtype-preserving)."""
    out = jnp.zeros((dp, 2 * hp), wk.dtype)
    out = out.at[:wk.shape[0], :d].set(wk[:, :d])
    out = out.at[:wk.shape[0], hp:hp + d].set(wk[:, d:])
    return out


def _pad_offsets(offsets, num_rows_padded):
    # padded rows are empty: their pointers repeat offsets[-1] (= real edges)
    pad = num_rows_padded + 1 - offsets.shape[0]
    return jnp.pad(offsets.astype(jnp.int32), (0, pad), mode="edge")


@functools.partial(jax.custom_vjp, nondiff_argnums=(11, 12, 13, 14, 15))
def _fused_atom_conv(v, e, e_a, w, b, ln_scale, ln_bias,
                     bond_center, bond_nbr, offsets, pair,
                     und, block_rows, chunk, gather_tile, residency):
    a_rows, dim = v.shape
    de = e.shape[1]
    n_edges = bond_center.shape[0]  # directed bond rows (chunk walk)
    d = w.shape[1] // 2
    # the wrapper splits w rows as [v_center | v_nbr | e] — fail loudly if
    # the caller's operand widths disagree with that partition
    assert w.shape[0] == 2 * dim + de, (w.shape, dim, de)
    dp = _round_up(max(dim, de), _LANE)
    hp = _round_up(d, _LANE)
    # atoms are both the output rows (block_rows tiles) and the in-kernel
    # nbr-gather table (gather_tile windows): pad to a common multiple
    ap = _round_up(a_rows, math.lcm(block_rows, gather_tile))
    ep = _round_up(n_edges, chunk)
    mirror = pair is not None
    assert mirror or not und, "und requires the pair mirror map"
    if und:
        # symmetric trunk (DESIGN.md §10): e itself is an Eu-row table
        # gathered in-kernel through bond_pair, like the e_a envelope
        e_p = _pad2(e, _round_up(e.shape[0], gather_tile), dp)
    else:
        assert e.shape[0] == n_edges, (e.shape, n_edges)
        e_p = _pad2(e, ep, dp)
    if mirror:
        # undirected store (DESIGN.md §5): e_a is an Eu-row table gathered
        # in-kernel through bond_pair — pad its rows to gather_tile windows
        ea_p = _pad2(e_a, _round_up(e_a.shape[0], gather_tile), hp)
        pair_ids = _pad_ids(pair, ep, chunk)
    else:
        ea_p = _pad2(e_a, ep, hp)
        pair_ids = _pad_ids(bond_center, ep, chunk)  # unused dummy
    # auto: padded table bytes (ids + v + e + e^a) vs the VMEM budget —
    # pure function of static shapes, so fwd and grad-of-fwd agree
    residency = _resolve_residency(
        residency,
        3 * _id_bytes(ep, chunk) + ap * dp * _itemsize(v.dtype)
        + e_p.shape[0] * dp * _itemsize(e.dtype)
        + ea_p.shape[0] * hp * _itemsize(e_a.dtype))
    out = fused_atom_conv_pallas(
        _pad2(v, ap, dp), e_p, ea_p,
        _pad_ids(bond_center, ep, chunk), _pad_ids(bond_nbr, ep, chunk),
        pair_ids,
        _pad_offsets(offsets, ap),
        _pack_lanes_w(w[:dim], dp, d, hp),
        _pack_lanes_w(w[dim:2 * dim], dp, d, hp),
        _pack_lanes_w(w[2 * dim:], dp, d, hp),
        _pack_lanes_vec(b, d, hp),
        _pack_lanes_vec(ln_scale, d, hp), _pack_lanes_vec(ln_bias, d, hp),
        d_real=d, block_rows=block_rows, chunk=chunk,
        gather_tile=gather_tile, mirror=mirror, und=und,
        residency=residency, interpret=_interpret(),
    )
    return out[:a_rows, :d].astype(v.dtype)


def _fused_atom_conv_fwd(v, e, e_a, w, b, ln_scale, ln_bias,
                         bond_center, bond_nbr, offsets, pair,
                         und, block_rows, chunk, gather_tile, residency):
    out = _fused_atom_conv(v, e, e_a, w, b, ln_scale, ln_bias,
                           bond_center, bond_nbr, offsets, pair,
                           und, block_rows, chunk, gather_tile, residency)
    # operands only — messages are rematerialized in the backward
    return out, (v, e, e_a, w, b, ln_scale, ln_bias,
                 bond_center, bond_nbr, offsets, pair)


def _fused_atom_conv_bwd(und, block_rows, chunk, gather_tile, residency,
                         res, g):
    """Tile-wise recompute backward: a fori_loop over edge chunks, each
    iteration re-deriving its (chunk, D) messages with a chunk-local
    jax.vjp — no full-edge concat/message tensor exists here either.
    With the mirror maps (``pair`` set), e_a cotangents accumulate into
    the Eu-row table (the chunk-local vjp's gather transposes to a
    table-shaped scatter-add).

    Residency-agnostic (DESIGN.md §9): the loop body touches one chunk of
    every edge operand via dynamic_slice and writes cotangents back with
    dynamic_update_slice, so XLA already streams HBM<->working-set chunk
    by chunk — exactly the semantics the hbm forward tier gets from its
    explicit DMA, with the Eu-table accumulation as the write stream."""
    (v, e, e_a, w, b, ln_scale, ln_bias, bond_center, bond_nbr, offsets,
     pair) = res
    n_edges = bond_center.shape[0]
    ep = _round_up(n_edges, chunk)
    seg_p = _pad_rows_i32(bond_center, ep)
    nbr_p = _pad_rows_i32(bond_nbr, ep)
    f32 = lambda x: x.astype(jnp.float32)
    v32, w32, b32 = f32(v), f32(w), f32(b)
    lns32, lnb32 = f32(ln_scale), f32(ln_bias)
    g32 = f32(g)
    n_real = offsets[-1].astype(jnp.int32)
    mirror = pair is not None
    if und:
        e_full = f32(e)     # (Eu, D) table — cotangents accumulate whole
    else:
        e_p = _pad_rows_f32(e, ep)
    if mirror:
        ea_full = f32(e_a)  # (Eu, D) table — cotangents accumulate whole
        pair_p = _pad_rows_i32(pair, ep)
    else:
        ea_p = _pad_rows_f32(e_a, ep)

    def body(k, carry):
        dv, dep_, dea, dw, db, dls, dlb = carry
        i0 = k * chunk
        seg_c = _chunk_of(seg_p, i0, chunk)
        nbr_c = _chunk_of(nbr_p, i0, chunk)
        if mirror:
            pair_c = _chunk_of(pair_p, i0, chunk)

        if und:
            def msgs(vv, e_t, ea_t, ww, bb, ss, oo):
                x = jnp.concatenate([vv[seg_c], vv[nbr_c], e_t[pair_c]],
                                    axis=-1)
                return ref.gated_mlp_packed_ref(x, ww, bb, ss, oo) \
                    * ea_t[pair_c]

            e_arg, ea_arg = e_full, ea_full
        elif mirror:
            def msgs(vv, ec, ea_t, ww, bb, ss, oo):
                x = jnp.concatenate([vv[seg_c], vv[nbr_c], ec], axis=-1)
                return ref.gated_mlp_packed_ref(x, ww, bb, ss, oo) \
                    * ea_t[pair_c]

            e_arg, ea_arg = _chunk_of(e_p, i0, chunk), ea_full
        else:
            def msgs(vv, ec, eac, ww, bb, ss, oo):
                x = jnp.concatenate([vv[seg_c], vv[nbr_c], ec], axis=-1)
                return ref.gated_mlp_packed_ref(x, ww, bb, ss, oo) * eac

            e_arg, ea_arg = _chunk_of(e_p, i0, chunk), \
                _chunk_of(ea_p, i0, chunk)

        _, vjp = jax.vjp(msgs, v32, e_arg, ea_arg, w32, b32, lns32, lnb32)
        valid = (i0 + jnp.arange(chunk)) < n_real
        gm = jnp.where(valid[:, None], g32[seg_c], 0.0)
        dvc, dec, deac, dwc, dbc, dlsc, dlbc = vjp(gm)
        dea = dea + deac if mirror else \
            jax.lax.dynamic_update_slice(dea, deac, (i0, 0))
        dep_ = dep_ + dec if und else \
            jax.lax.dynamic_update_slice(dep_, dec, (i0, 0))
        return (dv + dvc, dep_,
                dea, dw + dwc, db + dbc, dls + dlsc, dlb + dlbc)

    init = (jnp.zeros_like(v32),
            jnp.zeros_like(e_full) if und else jnp.zeros_like(e_p),
            jnp.zeros_like(ea_full) if mirror else jnp.zeros_like(ea_p),
            jnp.zeros_like(w32), jnp.zeros_like(b32),
            jnp.zeros_like(lns32), jnp.zeros_like(lnb32))
    # static trip count (padded chunks contribute masked zeros): the loop
    # lowers to scan, so the bwd itself stays reverse-differentiable — the
    # autodiff readout can run on top of the fused convs (forces need one
    # more reverse pass through this function)
    dv, dep_, dea, dw, db, dls, dlb = jax.lax.fori_loop(
        0, ep // chunk, body, init)
    dea = dea.astype(e_a.dtype) if mirror \
        else dea[:e.shape[0]].astype(e_a.dtype)
    de = dep_.astype(e.dtype) if und else dep_[:e.shape[0]].astype(e.dtype)
    f0 = jax.dtypes.float0
    return (dv.astype(v.dtype), de,
            dea, dw.astype(w.dtype),
            db.astype(b.dtype), dls.astype(ln_scale.dtype),
            dlb.astype(ln_bias.dtype),
            np.zeros(bond_center.shape, f0), np.zeros(bond_nbr.shape, f0),
            np.zeros(offsets.shape, f0),
            None if pair is None else np.zeros(pair.shape, f0))


_fused_atom_conv.defvjp(_fused_atom_conv_fwd, _fused_atom_conv_bwd)


def fused_atom_conv(v, e, e_a, w, b, ln_scale, ln_bias,
                    bond_center, bond_nbr, bond_offsets,
                    *, pair=None, und_features: bool = False,
                    block_rows: int = 8, chunk: int = 256,
                    gather_tile: int = 256, table_residency: str = "auto"):
    # block_rows=8: ~tens of bonds per atom, so 8 rows ~ one edge chunk
    """Fused Eq. 4 message path: sum_j e^a_ij * phi(v_i, v_j, e_ij) -> (A, D).

    Requires the sorted-segment layout (DESIGN.md §1): bonds sorted by
    ``bond_center`` with CSR ``bond_offsets``.  Forward is one Pallas
    megakernel (no HBM concat/message tensors); differentiable via a
    chunked recompute-in-backward custom VJP (DESIGN.md §3).

    ``pair`` (DESIGN.md §5): directed->undirected mirror map.  When set,
    ``e_a`` is the (Eu, D) undirected envelope table and the kernel
    gathers it per edge chunk in-register (mirror-indirected operand
    class) — the directed (E, D) expansion never exists in HBM.

    ``und_features`` (DESIGN.md §10): symmetric trunk — ``e`` is itself
    the (Eu, D) undirected bond table and gathers in-kernel through
    ``pair`` alongside ``e_a`` (requires ``pair``); the directed (E, D)
    expansion of the bond features never exists in HBM.

    ``table_residency`` (DESIGN.md §9): "vmem" keeps v/e/e^a whole-array
    resident; "hbm" leaves them in HBM and streams double-buffered DMA
    chunks/windows; "auto" picks by operand-table bytes vs the budget.
    """
    return _fused_atom_conv(v, e, e_a, w, b, ln_scale, ln_bias,
                            bond_center, bond_nbr, bond_offsets, pair,
                            und_features, block_rows, chunk, gather_tile,
                            table_residency)


@functools.partial(jax.custom_vjp, nondiff_argnums=(13, 14, 15, 16))
def _fused_bond_conv(v, e, a, e_b, w, b, ln_scale, ln_bias,
                     angle_ij, angle_ik, center_ids, offsets, pair,
                     block_rows, chunk, gather_tile, residency):
    a_rows, dim = v.shape
    b_rows = e.shape[0]
    e_rows = a.shape[0]
    d = w.shape[1] // 2
    # the wrapper splits w rows into four equal dim-wide blocks
    # [v_c | e_ij | e_ik | a]: all operand widths must equal dim
    assert e.shape[1] == dim and a.shape[1] == dim, \
        (v.shape, e.shape, a.shape)
    assert w.shape[0] == 4 * dim, (w.shape, dim)
    dp = _round_up(max(dim, e.shape[1], a.shape[1]), _LANE)
    hp = _round_up(d, _LANE)
    # bonds are output rows AND the ik-gather table; atoms the ctr-gather
    bp = _round_up(b_rows, math.lcm(block_rows, gather_tile))
    ap = _round_up(a_rows, gather_tile)
    ep = _round_up(e_rows, chunk)
    mirror = pair is not None
    if mirror:
        # undirected store (DESIGN.md §5): e_b is an Eu-row table; both
        # envelope gathers run in-kernel through bond_pair[angle_*] (cheap
        # int gathers here — no float tensor is expanded for them)
        eb_p = _pad2(e_b, _round_up(e_b.shape[0], gather_tile), hp)
        pij = _pad_ids(pair[angle_ij], ep, chunk)
        pik = _pad_ids(pair[angle_ik], ep, chunk)
    else:
        eb_p = _pad2(e_b, bp, hp)
        pij = _pad_ids(angle_ij, ep, chunk)   # unused dummies
        pik = _pad_ids(angle_ik, ep, chunk)
    residency = _resolve_residency(
        residency,
        5 * _id_bytes(ep, chunk) + ap * dp * _itemsize(v.dtype)
        + bp * dp * _itemsize(e.dtype) + ep * dp * _itemsize(a.dtype)
        + eb_p.shape[0] * hp * _itemsize(e_b.dtype))
    out = fused_bond_conv_pallas(
        _pad2(v, ap, dp), _pad2(e, bp, dp), _pad2(a, ep, dp), eb_p,
        _pad_ids(angle_ij, ep, chunk), _pad_ids(angle_ik, ep, chunk),
        _pad_ids(center_ids, ep, chunk), pij, pik,
        _pad_offsets(offsets, bp),
        _pack_lanes_w(w[:dim], dp, d, hp),
        _pack_lanes_w(w[dim:2 * dim], dp, d, hp),
        _pack_lanes_w(w[2 * dim:3 * dim], dp, d, hp),
        _pack_lanes_w(w[3 * dim:], dp, d, hp),
        _pack_lanes_vec(b, d, hp),
        _pack_lanes_vec(ln_scale, d, hp), _pack_lanes_vec(ln_bias, d, hp),
        d_real=d, block_rows=block_rows, chunk=chunk,
        gather_tile=gather_tile, mirror=mirror, residency=residency,
        interpret=_interpret(),
    )
    return out[:b_rows, :d].astype(e.dtype)


def _fused_bond_conv_fwd(v, e, a, e_b, w, b, ln_scale, ln_bias,
                         angle_ij, angle_ik, center_ids, offsets, pair,
                         block_rows, chunk, gather_tile, residency):
    out = _fused_bond_conv(v, e, a, e_b, w, b, ln_scale, ln_bias,
                           angle_ij, angle_ik, center_ids, offsets, pair,
                           block_rows, chunk, gather_tile, residency)
    return out, (v, e, a, e_b, w, b, ln_scale, ln_bias,
                 angle_ij, angle_ik, center_ids, offsets, pair)


def _fused_bond_conv_bwd(block_rows, chunk, gather_tile, residency, res, g):
    """Tile-wise recompute backward over angle chunks (see atom_conv).
    With the mirror maps, the envelope factors gather from the Eu-row
    table and their cotangents accumulate into it.  Residency-agnostic:
    chunk-local dynamic slices already stream (DESIGN.md §9)."""
    (v, e, a, e_b, w, b, ln_scale, ln_bias,
     angle_ij, angle_ik, center_ids, offsets, pair) = res
    e_rows = a.shape[0]
    ep = _round_up(e_rows, chunk)
    ij_p = _pad_rows_i32(angle_ij, ep)
    ik_p = _pad_rows_i32(angle_ik, ep)
    ctr_p = _pad_rows_i32(center_ids, ep)
    a_p = _pad_rows_f32(a, ep)
    f32 = lambda x: x.astype(jnp.float32)
    v32, e32, eb32, w32, b32 = f32(v), f32(e), f32(e_b), f32(w), f32(b)
    lns32, lnb32 = f32(ln_scale), f32(ln_bias)
    g32 = f32(g)
    n_real = offsets[-1].astype(jnp.int32)
    mirror = pair is not None
    if mirror:
        pij_p = _pad_rows_i32(pair[angle_ij], ep)
        pik_p = _pad_rows_i32(pair[angle_ik], ep)

    def body(k, carry):
        dv, de, dap, deb, dw, db, dls, dlb = carry
        i0 = k * chunk
        ij_c = _chunk_of(ij_p, i0, chunk)
        ik_c = _chunk_of(ik_p, i0, chunk)
        ctr_c = _chunk_of(ctr_p, i0, chunk)
        if mirror:
            pij_c = _chunk_of(pij_p, i0, chunk)
            pik_c = _chunk_of(pik_p, i0, chunk)
        else:
            pij_c, pik_c = ij_c, ik_c

        def msgs(vv, ee, ac, eb, ww, bb, ss, oo):
            x = jnp.concatenate([vv[ctr_c], ee[ij_c], ee[ik_c], ac], axis=-1)
            phi = ref.gated_mlp_packed_ref(x, ww, bb, ss, oo)
            return phi * eb[pij_c] * eb[pik_c]

        _, vjp = jax.vjp(msgs, v32, e32, _chunk_of(a_p, i0, chunk), eb32,
                         w32, b32, lns32, lnb32)
        valid = (i0 + jnp.arange(chunk)) < n_real
        gm = jnp.where(valid[:, None], g32[ij_c], 0.0)
        dvc, dec, dac, debc, dwc, dbc, dlsc, dlbc = vjp(gm)
        return (dv + dvc, de + dec,
                jax.lax.dynamic_update_slice(dap, dac, (i0, 0)),
                deb + debc, dw + dwc, db + dbc, dls + dlsc, dlb + dlbc)

    init = (jnp.zeros_like(v32), jnp.zeros_like(e32), jnp.zeros_like(a_p),
            jnp.zeros_like(eb32), jnp.zeros_like(w32), jnp.zeros_like(b32),
            jnp.zeros_like(lns32), jnp.zeros_like(lnb32))
    # static trip count -> scan -> reverse-differentiable (see atom_conv)
    dv, de, dap, deb, dw, db, dls, dlb = jax.lax.fori_loop(
        0, ep // chunk, body, init)
    f0 = jax.dtypes.float0
    return (dv.astype(v.dtype), de.astype(e.dtype),
            dap[:e_rows].astype(a.dtype), deb.astype(e_b.dtype),
            dw.astype(w.dtype), db.astype(b.dtype),
            dls.astype(ln_scale.dtype), dlb.astype(ln_bias.dtype),
            np.zeros(angle_ij.shape, f0), np.zeros(angle_ik.shape, f0),
            np.zeros(center_ids.shape, f0), np.zeros(offsets.shape, f0),
            None if pair is None else np.zeros(pair.shape, f0))


_fused_bond_conv.defvjp(_fused_bond_conv_fwd, _fused_bond_conv_bwd)


def fused_bond_conv(v, e, a, e_b, w, b, ln_scale, ln_bias,
                    angle_ij, angle_ik, center_ids, angle_offsets,
                    *, pair=None, block_rows: int = 32, chunk: int = 256,
                    gather_tile: int = 512, table_residency: str = "auto"):
    # block_rows=32: angles-per-bond is small (~1-5), so a wider row tile
    # keeps each program's edge range near one chunk instead of paying the
    # per-program gather-loop overhead for a handful of edges
    """Fused Eq. 5 message path:
    sum_k e^b_ij e^b_ik phi(v_c, e_ij, e_ik, a_ijk) -> (B, D).

    ``center_ids = bond_center[angle_ij]`` (a cheap int gather the caller
    performs; no float tensor is materialized for it).  Requires angles
    sorted by ``angle_ij`` with CSR ``angle_offsets`` (DESIGN.md §1).

    ``pair`` (DESIGN.md §5): directed->undirected mirror map.  When set,
    ``e_b`` is the (Eu, D) undirected envelope table; both envelope
    factors gather through ``pair[angle_*]`` inside the kernel.

    ``table_residency`` (DESIGN.md §9): "vmem" | "hbm" | "auto" as in
    ``fused_atom_conv`` — here the streamed tables are v/e/e^b plus the
    angle payload.
    """
    return _fused_bond_conv(v, e, a, e_b, w, b, ln_scale, ln_bias,
                            angle_ij, angle_ik, center_ids, angle_offsets,
                            pair, block_rows, chunk, gather_tile,
                            table_residency)


@functools.partial(jax.custom_vjp, nondiff_argnums=(14, 15, 16, 17, 18))
def _fused_sym_bond_conv(v, e, a_u, e_b, w, b, ln_scale, ln_bias,
                         ctr, du1, du2, rep, dest, offsets,
                         msg_block, block_rows, chunk, gather_tile,
                         residency):
    a_rows, dim = v.shape
    eu_rows = e.shape[0]
    ua_rows = a_u.shape[0]
    d = w.shape[1] // 2
    # the wrapper splits w rows into four equal dim-wide blocks
    # [v_c | e_ij | e_ik | a]; both e slots read the swap-symmetric e_s,
    # so w2 and w3 precombine into one GEMM block (DESIGN.md §10)
    assert e.shape[1] == dim and a_u.shape[1] == dim, \
        (v.shape, e.shape, a_u.shape)
    assert w.shape[0] == 4 * dim, (w.shape, dim)
    assert e_b.shape[0] == eu_rows, (e_b.shape, eu_rows)
    dp = _round_up(dim, _LANE)
    hp = _round_up(d, _LANE)
    ap = _round_up(a_rows, gather_tile)
    # Eu bonds are phase-B output rows AND a phase-A gather table; dedup
    # angles are phase-A output rows AND the phase-B msg-gather table
    eup = _round_up(eu_rows, math.lcm(block_rows, gather_tile))
    uap = _round_up(ua_rows, math.lcm(msg_block, gather_tile))
    icp = _round_up(dest.shape[0], chunk)
    # residency resolves per phase: A holds the v/e/e^b gather tables, B
    # the incidence ids plus the f32 message buffer
    res_a = _resolve_residency(
        residency,
        ap * dp * _itemsize(v.dtype) + eup * dp * _itemsize(e.dtype)
        + eup * hp * _itemsize(e_b.dtype))
    res_b = _resolve_residency(residency,
                               2 * _id_bytes(icp, chunk) + uap * hp * 4)
    msg = fused_sym_msg_pallas(
        _pad2(v, ap, dp), _pad2(e, eup, dp), _pad2(a_u, uap, dp),
        _pad2(e_b, eup, hp),
        _pad_ids(ctr, uap, msg_block), _pad_ids(du1, uap, msg_block),
        _pad_ids(du2, uap, msg_block),
        _pack_lanes_w(w[:dim], dp, d, hp),
        _pack_lanes_w(w[dim:2 * dim] + w[2 * dim:3 * dim], dp, d, hp),
        _pack_lanes_w(w[3 * dim:], dp, d, hp),
        _pack_lanes_vec(b, d, hp),
        _pack_lanes_vec(ln_scale, d, hp), _pack_lanes_vec(ln_bias, d, hp),
        d_real=d, msg_block=msg_block, gather_tile=gather_tile,
        residency=res_a, interpret=_interpret(),
    )
    agg = fused_sym_accum_pallas(
        msg, _pad_ids(dest, icp, chunk), _pad_ids(rep, icp, chunk),
        _pad_offsets(offsets, eup), eu_rows=eup, block_rows=block_rows,
        chunk=chunk, gather_tile=gather_tile, residency=res_b,
        interpret=_interpret(),
    )
    return agg[:eu_rows, :d].astype(e.dtype)


def _fused_sym_bond_conv_fwd(v, e, a_u, e_b, w, b, ln_scale, ln_bias,
                             ctr, du1, du2, rep, dest, offsets,
                             msg_block, block_rows, chunk, gather_tile,
                             residency):
    out = _fused_sym_bond_conv(v, e, a_u, e_b, w, b, ln_scale, ln_bias,
                               ctr, du1, du2, rep, dest, offsets,
                               msg_block, block_rows, chunk, gather_tile,
                               residency)
    return out, (v, e, a_u, e_b, w, b, ln_scale, ln_bias,
                 ctr, du1, du2, rep, dest, offsets)


def _fused_sym_bond_conv_bwd(msg_block, block_rows, chunk, gather_tile,
                             residency, res, g):
    """Tile-wise recompute backward over dedup-angle chunks (see
    atom_conv).  The incidence store is not walked here: each real Au row
    lands on exactly its two pair destinations, so the message cotangent
    is gm = g[du1] + g[du2] directly (self-image rows du1 == du2 read 2g,
    which is exactly their forward double-count)."""
    (v, e, a_u, e_b, w, b, ln_scale, ln_bias,
     ctr, du1, du2, rep, dest, offsets) = res
    ua_rows = a_u.shape[0]
    uap = _round_up(ua_rows, chunk)
    ctr_p = _pad_rows_i32(ctr, uap)
    du1_p = _pad_rows_i32(du1, uap)
    du2_p = _pad_rows_i32(du2, uap)
    a_p = _pad_rows_f32(a_u, uap)
    f32 = lambda x: x.astype(jnp.float32)
    v32, e32, eb32, w32, b32 = f32(v), f32(e), f32(e_b), f32(w), f32(b)
    lns32, lnb32 = f32(ln_scale), f32(ln_bias)
    g32 = f32(g)
    # each real dedup angle owns exactly TWO incidences (DESIGN.md §10)
    n_real = (offsets[-1] // 2).astype(jnp.int32)

    def body(k, carry):
        dv, de, dap, deb, dw, db, dls, dlb = carry
        i0 = k * chunk
        ctr_c = _chunk_of(ctr_p, i0, chunk)
        du1_c = _chunk_of(du1_p, i0, chunk)
        du2_c = _chunk_of(du2_p, i0, chunk)

        def msgs(vv, ee, ac, eb, ww, bb, ss, oo):
            es = ee[du1_c] + ee[du2_c]
            x = jnp.concatenate([vv[ctr_c], es, es, ac], axis=-1)
            phi = ref.gated_mlp_packed_ref(x, ww, bb, ss, oo)
            return phi * eb[du1_c] * eb[du2_c]

        _, vjp = jax.vjp(msgs, v32, e32, _chunk_of(a_p, i0, chunk), eb32,
                         w32, b32, lns32, lnb32)
        valid = (i0 + jnp.arange(chunk)) < n_real
        gm = jnp.where(valid[:, None], g32[du1_c] + g32[du2_c], 0.0)
        dvc, dec, dac, debc, dwc, dbc, dlsc, dlbc = vjp(gm)
        return (dv + dvc, de + dec,
                jax.lax.dynamic_update_slice(dap, dac, (i0, 0)),
                deb + debc, dw + dwc, db + dbc, dls + dlsc, dlb + dlbc)

    init = (jnp.zeros_like(v32), jnp.zeros_like(e32), jnp.zeros_like(a_p),
            jnp.zeros_like(eb32), jnp.zeros_like(w32), jnp.zeros_like(b32),
            jnp.zeros_like(lns32), jnp.zeros_like(lnb32))
    # static trip count -> scan -> reverse-differentiable (see atom_conv)
    dv, de, dap, deb, dw, db, dls, dlb = jax.lax.fori_loop(
        0, uap // chunk, body, init)
    f0 = jax.dtypes.float0
    return (dv.astype(v.dtype), de.astype(e.dtype),
            dap[:ua_rows].astype(a_u.dtype), deb.astype(e_b.dtype),
            dw.astype(w.dtype), db.astype(b.dtype),
            dls.astype(ln_scale.dtype), dlb.astype(ln_bias.dtype),
            np.zeros(ctr.shape, f0), np.zeros(du1.shape, f0),
            np.zeros(du2.shape, f0), np.zeros(rep.shape, f0),
            np.zeros(dest.shape, f0), np.zeros(offsets.shape, f0))


_fused_sym_bond_conv.defvjp(_fused_sym_bond_conv_fwd,
                            _fused_sym_bond_conv_bwd)


def fused_sym_bond_conv(v, e, a_u, e_b, w, b, ln_scale, ln_bias,
                        ctr, du1, du2, rep, dest, offsets,
                        *, msg_block: int = 256, block_rows: int = 32,
                        chunk: int = 256, gather_tile: int = 512,
                        table_residency: str = "auto"):
    """Fused symmetric-trunk Eq. 5 message path (DESIGN.md §10):

        msg_w  = e^b[du1] e^b[du2] phi(v_c, e_s, e_s, a_w),
        e_s    = e[du1] + e[du2],
        agg[u] = sum over incidences (u, w) of msg_w        -> (Eu, D)

    over the dedup angle rows, with one gated-MLP evaluation per
    UNDIRECTED angle — half the directed count — scattered to BOTH
    undirected bonds of its pair through the sym-incidence store
    (``dest``/``rep`` sorted by destination, CSR ``offsets``).  Two
    launches: a phase-A message kernel over Au blocks and a phase-B
    destination-tiled accumulator over Eu blocks; splitting at the
    scatter is what keeps phi evaluated once per angle.

    ``ctr = bond_center[und_angle_ij]``, ``du1/du2 = bond_pair[
    und_angle_ij/ik]`` (cheap int gathers the caller performs).

    ``table_residency`` (DESIGN.md §9): "vmem" | "hbm" | "auto",
    resolved independently for each phase.
    """
    return _fused_sym_bond_conv(v, e, a_u, e_b, w, b, ln_scale, ln_bias,
                                ctr, du1, du2, rep, dest, offsets,
                                msg_block, block_rows, chunk, gather_tile,
                                table_residency)


@functools.partial(jax.custom_vjp, nondiff_argnums=(8, 9, 10, 11))
def _fused_force_readout(e, x_hat, w1, b1, w2, b2, bond_center, offsets,
                         num_atoms, block_rows, chunk, residency):
    e_rows, dim = e.shape
    dp = _round_up(dim, _LANE)
    xp = _LANE
    ap = _round_up(num_atoms, block_rows)
    ep = _round_up(e_rows, chunk)
    residency = _resolve_residency(
        residency, _id_bytes(ep, chunk) + ep * dp * _itemsize(e.dtype)
        + ep * xp * _itemsize(x_hat.dtype))
    out = fused_force_readout_pallas(
        _pad2(e, ep, dp), _pad2(x_hat, ep, xp),
        _pad_ids(bond_center, ep, chunk), _pad_offsets(offsets, ap),
        _pad2(w1, dp, dp), _pad2(b1[None, :], 1, dp),
        _pad2(w2.T, 1, dp), jnp.full((1, xp), b2[0], b2.dtype),
        block_rows=block_rows, chunk=chunk, residency=residency,
        interpret=_interpret(),
    )
    return out[:num_atoms, :x_hat.shape[1]].astype(e.dtype)


def _fused_force_readout_fwd(e, x_hat, w1, b1, w2, b2, bond_center, offsets,
                             num_atoms, block_rows, chunk, residency):
    out = _fused_force_readout(e, x_hat, w1, b1, w2, b2, bond_center,
                               offsets, num_atoms, block_rows, chunk,
                               residency)
    return out, (e, x_hat, w1, b1, w2, b2, bond_center, offsets)


def _fused_force_readout_bwd(num_atoms, block_rows, chunk, residency,
                             res, g):
    """Tile-wise recompute backward over bond chunks (see atom_conv).
    Residency-agnostic: chunk-local dynamic slices already stream."""
    e, x_hat, w1, b1, w2, b2, bond_center, offsets = res
    e_rows = e.shape[0]
    ep = _round_up(e_rows, chunk)
    seg_p = _pad_rows_i32(bond_center, ep)
    e_p = _pad_rows_f32(e, ep)
    xh_p = _pad_rows_f32(x_hat, ep)
    f32 = lambda x: x.astype(jnp.float32)
    w1_32, b1_32, w2_32, b2_32 = f32(w1), f32(b1), f32(w2), f32(b2)
    g32 = f32(g)
    n_real = offsets[-1].astype(jnp.int32)

    def body(k, carry):
        dep_, dxhp, dw1, db1, dw2, db2 = carry
        i0 = k * chunk
        seg_c = _chunk_of(seg_p, i0, chunk)

        def contribs(ec, xc, w1_, b1_, w2_, b2_):
            h = jax.nn.silu(ec @ w1_ + b1_)
            return (h @ w2_ + b2_) * xc

        _, vjp = jax.vjp(contribs, _chunk_of(e_p, i0, chunk),
                         _chunk_of(xh_p, i0, chunk),
                         w1_32, b1_32, w2_32, b2_32)
        valid = (i0 + jnp.arange(chunk)) < n_real
        gm = jnp.where(valid[:, None], g32[seg_c], 0.0)
        dec, dxc, dw1c, db1c, dw2c, db2c = vjp(gm)
        return (jax.lax.dynamic_update_slice(dep_, dec, (i0, 0)),
                jax.lax.dynamic_update_slice(dxhp, dxc, (i0, 0)),
                dw1 + dw1c, db1 + db1c, dw2 + dw2c, db2 + db2c)

    init = (jnp.zeros_like(e_p), jnp.zeros_like(xh_p),
            jnp.zeros_like(w1_32), jnp.zeros_like(b1_32),
            jnp.zeros_like(w2_32), jnp.zeros_like(b2_32))
    # static trip count -> scan -> reverse-differentiable (see atom_conv)
    dep_, dxhp, dw1, db1, dw2, db2 = jax.lax.fori_loop(
        0, ep // chunk, body, init)
    f0 = jax.dtypes.float0
    return (dep_[:e_rows].astype(e.dtype), dxhp[:e_rows].astype(x_hat.dtype),
            dw1.astype(w1.dtype), db1.astype(b1.dtype),
            dw2.astype(w2.dtype), db2.astype(b2.dtype),
            np.zeros(bond_center.shape, f0), np.zeros(offsets.shape, f0))


_fused_force_readout.defvjp(_fused_force_readout_fwd,
                            _fused_force_readout_bwd)


def fused_force_readout(e, x_hat, w1, b1, w2, b2, bond_center, bond_offsets,
                        num_atoms: int, *, block_rows: int = 8,
                        chunk: int = 256, table_residency: str = "auto"):
    """Fused Eq. 7 direct-force readout: F_i = sum_j n_ij x_hat_ij -> (A, 3).

    The per-bond scalar MLP (w1/b1 -> silu -> w2/b2), the x_hat weighting,
    and the per-atom reduction run in one megakernel over the sorted CSR
    rows; ``n_ij`` never exists in HBM.  Rotation equivariance (Eq. 8) is
    preserved because ``n_ij`` stays a scalar per bond.

    ``table_residency`` (DESIGN.md §9): "vmem" | "hbm" | "auto" — the
    streamed operands here are the bond features and x_hat payload.
    """
    return _fused_force_readout(e, x_hat, w1, b1, w2, b2, bond_center,
                                bond_offsets, num_atoms, block_rows, chunk,
                                table_residency)


@functools.partial(jax.custom_vjp, nondiff_argnums=(10, 11, 12, 13, 14))
def _fused_force_virial_readout(e, x_hat, dist, w1, b1, w2, b2, bond_center,
                                bond_crystal, offsets, num_atoms,
                                num_crystals, block_rows, chunk, residency):
    e_rows, dim = e.shape
    dp = _round_up(dim, _LANE)
    xp = _LANE
    ap = _round_up(num_atoms, block_rows)
    bp = _round_up(num_crystals, block_rows)
    ep = _round_up(e_rows, chunk)
    # the bond distance rides in x_hat's padding lane _DIST_LANE (one
    # payload stream instead of a one-lane column)
    xh_p = _pad2(x_hat, ep, xp).at[:e_rows, _DIST_LANE].set(
        dist.astype(x_hat.dtype))
    residency = _resolve_residency(
        residency, 2 * _id_bytes(ep, chunk) + ep * dp * _itemsize(e.dtype)
        + ep * xp * _itemsize(x_hat.dtype))
    out, sig = fused_force_readout_pallas(
        _pad2(e, ep, dp), xh_p,
        _pad_ids(bond_center, ep, chunk), _pad_offsets(offsets, ap),
        _pad2(w1, dp, dp), _pad2(b1[None, :], 1, dp),
        _pad2(w2.T, 1, dp), jnp.full((1, xp), b2[0], b2.dtype),
        cry=_pad_ids(bond_crystal, ep, chunk), num_crystals=bp,
        virial=True, block_rows=block_rows, chunk=chunk,
        residency=residency, interpret=_interpret(),
    )
    forces = out[:num_atoms, :x_hat.shape[1]].astype(e.dtype)
    # accumulator lanes are [m*128 + n] (DESIGN.md §7); stays f32 (§4)
    raw = sig[:num_crystals].reshape(num_crystals, 3, _LANE)[:, :, :3]
    return forces, raw


def _fused_force_virial_readout_fwd(e, x_hat, dist, w1, b1, w2, b2,
                                    bond_center, bond_crystal, offsets,
                                    num_atoms, num_crystals, block_rows,
                                    chunk, residency):
    out = _fused_force_virial_readout(e, x_hat, dist, w1, b1, w2, b2,
                                      bond_center, bond_crystal, offsets,
                                      num_atoms, num_crystals, block_rows,
                                      chunk, residency)
    return out, (e, x_hat, dist, w1, b1, w2, b2, bond_center, bond_crystal,
                 offsets)


def _fused_force_virial_readout_bwd(num_atoms, num_crystals, block_rows,
                                    chunk, residency, res, g):
    """Tile-wise recompute backward over bond chunks with DUAL cotangents:
    each chunk re-derives its (chunk, 3) force and (chunk, 9) virial
    contributions with one chunk-local jax.vjp, gathers the force
    cotangent through bond_center and the stress cotangent through
    bond_crystal, and masks both by edge validity (DESIGN.md §7)."""
    (e, x_hat, dist, w1, b1, w2, b2, bond_center, bond_crystal,
     offsets) = res
    g_f, g_s = g
    e_rows = e.shape[0]
    ep = _round_up(e_rows, chunk)
    seg_p = _pad_rows_i32(bond_center, ep)
    cry_p = _pad_rows_i32(bond_crystal, ep)
    e_p = _pad_rows_f32(e, ep)
    xh_p = _pad_rows_f32(x_hat, ep)
    dist_p = jnp.pad(dist.astype(jnp.float32), (0, ep - e_rows))
    f32 = lambda x: x.astype(jnp.float32)
    w1_32, b1_32, w2_32, b2_32 = f32(w1), f32(b1), f32(w2), f32(b2)
    gf32 = f32(g_f)
    gs32 = f32(g_s).reshape(num_crystals, 9)
    n_real = offsets[-1].astype(jnp.int32)

    def body(k, carry):
        dep_, dxhp, ddp, dw1, db1, dw2, db2 = carry
        i0 = k * chunk
        seg_c = _chunk_of(seg_p, i0, chunk)
        cry_c = _chunk_of(cry_p, i0, chunk)

        def contribs(ec, xc, dc, w1_, b1_, w2_, b2_):
            h = jax.nn.silu(ec @ w1_ + b1_)
            n = h @ w2_ + b2_                       # (chunk, 1)
            outer = (xc[:, :, None] * xc[:, None, :]).reshape(chunk, 9)
            return n * xc, (n * dc[:, None]) * outer

        _, vjp = jax.vjp(contribs, _chunk_of(e_p, i0, chunk),
                         _chunk_of(xh_p, i0, chunk),
                         _chunk_of(dist_p, i0, chunk),
                         w1_32, b1_32, w2_32, b2_32)
        valid = (i0 + jnp.arange(chunk)) < n_real
        gm_f = jnp.where(valid[:, None], gf32[seg_c], 0.0)
        gm_s = jnp.where(valid[:, None], gs32[cry_c], 0.0)
        dec, dxc, ddc, dw1c, db1c, dw2c, db2c = vjp((gm_f, gm_s))
        return (jax.lax.dynamic_update_slice(dep_, dec, (i0, 0)),
                jax.lax.dynamic_update_slice(dxhp, dxc, (i0, 0)),
                jax.lax.dynamic_update_slice(ddp, ddc, (i0,)),
                dw1 + dw1c, db1 + db1c, dw2 + dw2c, db2 + db2c)

    init = (jnp.zeros_like(e_p), jnp.zeros_like(xh_p),
            jnp.zeros_like(dist_p),
            jnp.zeros_like(w1_32), jnp.zeros_like(b1_32),
            jnp.zeros_like(w2_32), jnp.zeros_like(b2_32))
    # static trip count -> scan -> reverse-differentiable (see atom_conv)
    dep_, dxhp, ddp, dw1, db1, dw2, db2 = jax.lax.fori_loop(
        0, ep // chunk, body, init)
    f0 = jax.dtypes.float0
    return (dep_[:e_rows].astype(e.dtype), dxhp[:e_rows].astype(x_hat.dtype),
            ddp[:e_rows].astype(dist.dtype),
            dw1.astype(w1.dtype), db1.astype(b1.dtype),
            dw2.astype(w2.dtype), db2.astype(b2.dtype),
            np.zeros(bond_center.shape, f0),
            np.zeros(bond_crystal.shape, f0),
            np.zeros(offsets.shape, f0))


_fused_force_virial_readout.defvjp(_fused_force_virial_readout_fwd,
                                   _fused_force_virial_readout_bwd)


def fused_force_virial_readout(e, x_hat, dist, w1, b1, w2, b2, bond_center,
                               bond_crystal, bond_offsets, num_atoms: int,
                               num_crystals: int, *, block_rows: int = 8,
                               chunk: int = 256,
                               table_residency: str = "auto"):
    """Single-pass Eq. 7 force readout + per-bond virial stress epilogue.

    One kernel launch produces BOTH outputs (DESIGN.md §7): the (A, 3)
    forces of ``fused_force_readout`` and the raw (B, 3, 3) f32 per-crystal
    virial partials ``sum n_ij d_ij x_hat ⊗ x_hat`` — accumulated in the
    same tile walk while ``n_ij``/``x_hat`` are VMEM-resident, so the
    stress path costs zero extra HBM reads of ``e``/``vec`` and the
    (E, 3, 3) outer-product tensor never materializes.  Volume
    normalization / unit conversion live in ``core.heads`` (the kernel
    boundary carries raw sums only).  Differentiable via a chunked
    recompute custom VJP emitting cotangents for both outputs.

    ``table_residency`` (DESIGN.md §9): as in ``fused_force_readout``,
    with the crystal ids and per-bond distances as extra streams.
    """
    return _fused_force_virial_readout(e, x_hat, dist, w1, b1, w2, b2,
                                       bond_center, bond_crystal,
                                       bond_offsets, num_atoms, num_crystals,
                                       block_rows, chunk, table_residency)


def fused_swiglu(x, w_gate, w_up, w_down, *, activation: str = "silu",
                 block_m: int = 128, block_f: int = 256):
    """LM gated MLP: (M, D) -> (M, D), whole MLP in one kernel."""
    x_p, m = _pad_rows(x, block_m)
    out = fused_swiglu_pallas(
        x_p, w_gate, w_up, w_down, activation=activation,
        block_m=block_m, block_f=block_f, interpret=_interpret(),
    )
    return out[:m]


def flash_attention(q, k, v, *, causal: bool = True, scale=None,
                    block_q: int = 128, block_k: int = 128):
    """(B, H, S, D) flash attention; folds B,H into the grid."""
    b, h, sq, d = q.shape
    sk = k.shape[2]
    qf = q.reshape(b * h, sq, d)
    kf = k.reshape(b * h, sk, d)
    vf = v.reshape(b * h, sk, d)
    out = flash_attention_pallas(
        qf, kf, vf, causal=causal, scale=scale,
        block_q=block_q, block_k=block_k, interpret=_interpret(),
    )
    return out.reshape(b, h, sq, d)

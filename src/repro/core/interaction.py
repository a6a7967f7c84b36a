"""Interaction block: GatedMLP, AtomConv, BondConv, AngleUpdate.

Implements BOTH block variants (paper Eq. 10 vs Eq. 11):

  - ``reference``: BondConv consumes v^{t+1}; AngleUpdate consumes v^{t+1}
    and e^{t+1} (sequential dependency chain, as in CHGNet v0.3.0).
  - ``fast``: dependency elimination (FastCHGNet C2) — BondConv and
    AngleUpdate consume the layer-t features, so the three updates are
    data-independent and XLA can schedule them concurrently.

GatedMLP phi(x) = sigmoid(LN(x@Wg+bg)) * silu(LN(x@Wc+bc))   (paper §II-B)
with three implementations:
  - ``ref``    : two separate GEMMs + two LNs (reference graph)
  - ``packed`` : one GEMM against [Wc ‖ Wg] (+ single fused epilogue),
                 the Fig. 3 packing in pure jnp — what XLA sees on TPU
  - ``pallas`` : the hand-fused Pallas kernel (repro.kernels.fused_gated_mlp)

GatedMLP parameters are STORED pre-packed (``w = [Wc ‖ Wg]``,
``b``/``ln_scale``/``ln_bias`` = ``[core ‖ gate]``): the Fig. 3(a) concat
happens once at init (or once at checkpoint load, see
``pack_gated_mlp_params``), never inside a jitted step.  ``impl="ref"``
slices the halves back out; slicing is free under XLA, re-concatenating
per step was not.

On top of the per-call-site impl choices, ``conv_impl="fused"`` (DESIGN.md
§3) replaces the whole gather -> GatedMLP -> envelope -> reduce message
path of atom_conv / bond_conv with one Pallas megakernel over the sorted
CSR rows (requires DESIGN.md §1), so the (E, 3D)/(A_ang, 4D) concats and
(E, D) messages never reach HBM and are never saved for the backward.

``bond_store="undirected"`` (DESIGN.md §5) hands the convs e^a/e^b at the
undirected capacity Eu ~ E/2; they are expanded through the batch's
``bond_pair`` mirror map — an explicit gather in the unfused path, the
mirror-indirected operand class inside the megakernels when fused.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .graph import CrystalGraphBatch


def _glorot(key, shape, dtype=jnp.float32):
    fan_in, fan_out = shape[0], shape[-1]
    scale = jnp.sqrt(2.0 / (fan_in + fan_out))
    return jax.random.normal(key, shape, dtype) * scale


def linear_init(key, d_in, d_out, dtype=jnp.float32):
    return {
        "w": _glorot(key, (d_in, d_out), dtype),
        "b": jnp.zeros((d_out,), dtype),
    }


def dot_accum(x, w, accum_dtype=jnp.float32):
    """x @ w with MXU accumulation pinned to ``accum_dtype`` and the result
    cast back to x's dtype (DESIGN.md §4 kernel-accumulator rule).  For f32
    operands this is exactly ``x @ w``."""
    out = jax.lax.dot_general(
        x, w, (((x.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=accum_dtype)
    return out.astype(x.dtype)


def linear_apply(p, x):
    # cast-to-compute view: params are stored in param_dtype and cast to
    # the activation dtype at the use site (free under f32, DESIGN.md §4)
    return dot_accum(x, p["w"].astype(x.dtype)) + p["b"].astype(x.dtype)


def layer_norm(x, scale, bias, eps=1e-5):
    # statistics pinned to accum (f32): bf16 mean/var would lose ~2 digits
    # on the D-length reductions (DESIGN.md §4)
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    y = y * scale.astype(jnp.float32) + bias.astype(jnp.float32)
    return y.astype(x.dtype)


# ---------------------------------------------------------------------------
# GatedMLP
# ---------------------------------------------------------------------------

def gated_mlp_init(key, d_in, d_out, dtype=jnp.float32):
    """Packed storage layout: the Fig. 3(a) concat happens HERE, once.

    Each half is glorot-initialized with its own fan-out (identical
    statistics to the legacy separate-weight layout) and packed so no step
    function ever re-concatenates parameters.
    """
    kc, kg = jax.random.split(key)
    return {
        "w": jnp.concatenate(
            [_glorot(kc, (d_in, d_out), dtype),
             _glorot(kg, (d_in, d_out), dtype)], axis=1),
        "b": jnp.zeros((2 * d_out,), dtype),
        "ln_scale": jnp.ones((2 * d_out,), dtype),
        "ln_bias": jnp.zeros((2 * d_out,), dtype),
    }


_LEGACY_GATED_KEYS = frozenset(
    ("wc", "bc", "wg", "bg",
     "ln_c_scale", "ln_c_bias", "ln_g_scale", "ln_g_bias"))


def pack_gated_mlp_params(tree):
    """Convert legacy separate-weight GatedMLP dicts into the packed layout.

    Walks an arbitrary pytree (params, Adam moments, full Trainer state)
    and packs every dict whose keys are exactly the legacy GatedMLP set —
    the checkpoint-load half of the "pack once" policy.
    """
    if isinstance(tree, dict):
        if set(tree.keys()) == _LEGACY_GATED_KEYS:
            return {
                "w": jnp.concatenate([tree["wc"], tree["wg"]], axis=1),
                "b": jnp.concatenate([tree["bc"], tree["bg"]], axis=0),
                "ln_scale": jnp.concatenate(
                    [tree["ln_c_scale"], tree["ln_g_scale"]], axis=0),
                "ln_bias": jnp.concatenate(
                    [tree["ln_c_bias"], tree["ln_g_bias"]], axis=0),
            }
        return {k: pack_gated_mlp_params(v) for k, v in tree.items()}
    if isinstance(tree, list):
        return [pack_gated_mlp_params(v) for v in tree]
    if isinstance(tree, tuple):
        return tuple(pack_gated_mlp_params(v) for v in tree)
    return tree


def gated_mlp_legacy_template(tree):
    """Packed pytree -> legacy-layout template (for restoring old
    checkpoints: restore into this, then ``pack_gated_mlp_params``)."""
    if isinstance(tree, dict):
        if set(tree.keys()) == {"w", "b", "ln_scale", "ln_bias"}:
            d = tree["w"].shape[1] // 2
            return {
                "wc": tree["w"][:, :d], "wg": tree["w"][:, d:],
                "bc": tree["b"][:d], "bg": tree["b"][d:],
                "ln_c_scale": tree["ln_scale"][:d],
                "ln_g_scale": tree["ln_scale"][d:],
                "ln_c_bias": tree["ln_bias"][:d],
                "ln_g_bias": tree["ln_bias"][d:],
            }
        return {k: gated_mlp_legacy_template(v) for k, v in tree.items()}
    if isinstance(tree, list):
        return [gated_mlp_legacy_template(v) for v in tree]
    if isinstance(tree, tuple):
        return tuple(gated_mlp_legacy_template(v) for v in tree)
    return tree


def gated_mlp_apply(p, x, impl: str = "packed"):
    d = p["w"].shape[1] // 2
    w = p["w"].astype(x.dtype)  # cast-to-compute view (DESIGN.md §4)
    b = p["b"].astype(x.dtype)
    if impl == "ref":
        core = layer_norm(dot_accum(x, w[:, :d]) + b[:d],
                          p["ln_scale"][:d], p["ln_bias"][:d])
        gate = layer_norm(dot_accum(x, w[:, d:]) + b[d:],
                          p["ln_scale"][d:], p["ln_bias"][d:])
        return jax.nn.silu(core) * jax.nn.sigmoid(gate)
    if impl == "packed":
        # Fig. 3(a): one GEMM against the pre-packed weights (packed at
        # init, not here); Fig. 3(b): shared epilogue, silu(x) =
        # x * sigmoid(x) reuses the sigmoid.
        y = dot_accum(x, w) + b
        core, gate = y[..., :d], y[..., d:]
        core = layer_norm(core, p["ln_scale"][:d], p["ln_bias"][:d])
        gate = layer_norm(gate, p["ln_scale"][d:], p["ln_bias"][d:])
        sg_core = jax.nn.sigmoid(core)
        sg_gate = jax.nn.sigmoid(gate)
        return (core * sg_core) * sg_gate
    if impl == "pallas":
        from repro.kernels import ops as kops  # lazy: avoid import cycle

        return kops.fused_gated_mlp_packed(
            x, w, b, p["ln_scale"], p["ln_bias"])
    raise ValueError(f"unknown GatedMLP impl {impl!r}")


# ---------------------------------------------------------------------------
# Aggregation engine: one masked segment sum, four implementations
# ---------------------------------------------------------------------------

def segment_aggregate(values, segment_ids, num_segments, mask, impl="scatter",
                      *, offsets=None, table_residency: str = "auto"):
    """sum_{e : seg(e)=s} values[e] * mask[e]  -> (num_segments, D).

    The one aggregation engine every reduction in the model routes through
    (atom_conv, bond_conv, the direct force head).  Implementation matrix
    in DESIGN.md §2:

    impl="scatter": jax segment_sum (scatter-add; reference).
    impl="matmul" : one-hot matmul — O(E*S) FLOPs but runs on the MXU with
        no scatter; wins for the small segment counts of CHGNet batches.
    impl="sorted" : requires real ids sorted by segment (DESIGN.md §1, no
        CSR arrays needed).  Pure-jnp: remaps the padded tail onto the
        last segment so the whole id array is non-decreasing, then lets XLA
        lower a sorted segment_sum (``indices_are_sorted=True`` — no
        unsorted-scatter fallback).
    impl="pallas" : the fused tiled reduction kernel
        (``repro.kernels.fused_segment_sum``) — deterministic, atomics-free,
        MXU-tiled over the CSR rows.

    Precision (DESIGN.md §4): the reduction ACCUMULATES in f32 regardless
    of the operand dtype — bf16 edge payloads sum into f32 partials (the
    MXU's native behavior; pinned here so scatter/sorted match on every
    backend) — and the result is cast back to the operand dtype.

    ``table_residency`` (DESIGN.md §9, impl="pallas" only): "vmem" keeps
    the edge operands whole-array resident, "hbm" streams them with
    double-buffered DMA, "auto" picks by operand bytes vs the budget.
    """
    v = values * mask[..., None].astype(values.dtype)
    if impl == "scatter":
        return jax.ops.segment_sum(
            v.astype(jnp.float32), segment_ids, num_segments=num_segments
        ).astype(values.dtype)
    if impl == "matmul":
        onehot = jax.nn.one_hot(segment_ids, num_segments, dtype=values.dtype)
        return jnp.einsum(
            "es,ed->sd", onehot, v, preferred_element_type=jnp.float32
        ).astype(values.dtype)
    if impl == "sorted":
        # padded tail ids are 0 by the padding convention; point them at
        # the last segment (their payload is masked to zero) so the full
        # array really is sorted before asserting it to XLA
        ids = jnp.where(mask > 0, segment_ids, num_segments - 1)
        return jax.ops.segment_sum(
            v.astype(jnp.float32), ids, num_segments=num_segments,
            indices_are_sorted=True
        ).astype(values.dtype)
    if impl == "pallas":
        if offsets is None:
            raise ValueError(
                'impl="pallas" needs CSR offsets (sorted-segment layout); '
                "pack batches through repro.batching to get them"
            )
        from repro.kernels import ops as kops  # lazy: avoid import cycle

        return kops.fused_segment_sum(v, segment_ids, offsets, num_segments,
                                      table_residency=table_residency)
    raise ValueError(f"unknown aggregate impl {impl!r}")


# ---------------------------------------------------------------------------
# Interaction block
# ---------------------------------------------------------------------------

def interaction_block_init(key, dim=64, dtype=jnp.float32):
    ks = jax.random.split(key, 6)
    return {
        "atom_mlp": gated_mlp_init(ks[0], 3 * dim, dim, dtype),
        "atom_out": linear_init(ks[1], dim, dim, dtype),
        "bond_mlp": gated_mlp_init(ks[2], 4 * dim, dim, dtype),
        "bond_out": linear_init(ks[3], dim, dim, dtype),
        "angle_mlp": gated_mlp_init(ks[4], 4 * dim, dim, dtype),
    }


def atom_conv(p, graph: CrystalGraphBatch, v, e, e_a, *, mlp_impl, agg_impl,
              conv_impl: str = "unfused", bond_store: str = "directed",
              bond_features: str = "directed",
              table_residency: str = "auto"):
    """Eq. 4: v_i <- v_i + L_v[ sum_j e^a_ij * phi(v_i, v_j, e_ij) ].

    ``conv_impl="fused"`` runs the whole message path (gather -> GatedMLP
    -> envelope -> reduce) as one Pallas megakernel over the sorted CSR
    rows (DESIGN.md §3; requires §1; ``mlp_impl``/``agg_impl`` are
    subsumed).  ``"unfused"`` keeps the composable impl matrix below.

    ``bond_store="undirected"`` (DESIGN.md §5): ``e_a`` lives at the
    undirected capacity and is gathered through ``graph.bond_pair`` — in
    the unfused path explicitly, in the fused path inside the megakernel
    (the mirror-indirected operand class).  The envelope is symmetric
    (e^a_ij == e^a_ji, a function of |r_ij| only), so no sign is applied.

    ``bond_features="undirected"`` (DESIGN.md §10): ``e`` too lives at the
    undirected capacity (e_ij == e_ji in the symmetric trunk) and joins
    e^a in the mirror-indirected operand class; per-bond messages still
    run at E rows because v_i/v_j differ across the two directions.

    ``table_residency`` (DESIGN.md §9): operand-table residency tier of
    the fused/pallas kernels ("vmem" | "hbm" | "auto").
    """
    if conv_impl == "fused":
        from repro.kernels import ops as kops  # lazy: avoid import cycle

        # cast-to-compute view of the MLP params: kernel VMEM operands all
        # share the activation dtype (DESIGN.md §4); no-op under f32
        mlp = jax.tree.map(lambda t: t.astype(v.dtype), p["atom_mlp"])
        agg = kops.fused_atom_conv(
            v, e, e_a, mlp["w"], mlp["b"], mlp["ln_scale"], mlp["ln_bias"],
            graph.bond_center, graph.bond_nbr, graph.bond_offsets,
            pair=graph.bond_pair if bond_store == "undirected" else None,
            und_features=bond_features == "undirected",
            table_residency=table_residency,
        )
    elif conv_impl == "unfused":
        e_dir = e[graph.bond_pair] if bond_features == "undirected" else e
        f_v = jnp.concatenate(
            [v[graph.bond_center], v[graph.bond_nbr], e_dir], axis=-1
        )
        env = e_a[graph.bond_pair] if bond_store == "undirected" else e_a
        msg = gated_mlp_apply(p["atom_mlp"], f_v, mlp_impl) * env
        agg = segment_aggregate(
            msg, graph.bond_center, graph.atom_cap, graph.bond_mask, agg_impl,
            offsets=graph.bond_offsets, table_residency=table_residency,
        )
    else:
        raise ValueError(f"unknown conv impl {conv_impl!r}")
    mask = graph.atom_mask[..., None].astype(v.dtype)
    return v + linear_apply(p["atom_out"], agg) * mask


def bond_conv(p, graph: CrystalGraphBatch, v_in, e, a, e_b, *, mlp_impl,
              agg_impl, conv_impl: str = "unfused",
              bond_store: str = "directed",
              table_residency: str = "auto"):
    """Eq. 5: e_ij <- e_ij + L_e[ sum_k e^b_ij * e^b_ik * phi(f_e) ].

    ``v_in`` is v^{t+1} in the reference variant, v^t in the fast variant.
    ``conv_impl`` as in ``atom_conv`` (DESIGN.md §3).

    ``bond_store="undirected"`` (DESIGN.md §5): ``e_b`` lives at the
    undirected capacity; both envelope factors gather through
    ``bond_pair[angle_*]`` (explicitly here, inside the megakernel when
    fused).  Like e^a, e^b is symmetric, so no sign is applied.
    """
    center = graph.bond_center[graph.angle_ij]
    if conv_impl == "fused":
        from repro.kernels import ops as kops  # lazy: avoid import cycle

        mlp = jax.tree.map(lambda t: t.astype(e.dtype), p["bond_mlp"])
        agg = kops.fused_bond_conv(
            v_in, e, a, e_b, mlp["w"], mlp["b"], mlp["ln_scale"],
            mlp["ln_bias"], graph.angle_ij, graph.angle_ik, center,
            graph.angle_offsets,
            pair=graph.bond_pair if bond_store == "undirected" else None,
            table_residency=table_residency,
        )
    elif conv_impl == "unfused":
        f_e = jnp.concatenate(
            [v_in[center], e[graph.angle_ij], e[graph.angle_ik], a], axis=-1
        )
        msg = gated_mlp_apply(p["bond_mlp"], f_e, mlp_impl)
        if bond_store == "undirected":
            msg = msg * e_b[graph.bond_pair[graph.angle_ij]] \
                * e_b[graph.bond_pair[graph.angle_ik]]
        else:
            msg = msg * e_b[graph.angle_ij] * e_b[graph.angle_ik]
        agg = segment_aggregate(
            msg, graph.angle_ij, graph.bond_cap, graph.angle_mask, agg_impl,
            offsets=graph.angle_offsets, table_residency=table_residency,
        )
    else:
        raise ValueError(f"unknown conv impl {conv_impl!r}")
    mask = graph.bond_mask[..., None].astype(e.dtype)
    return e + linear_apply(p["bond_out"], agg) * mask


def angle_update(p, graph: CrystalGraphBatch, v_in, e_in, a, *, mlp_impl):
    """Eq. 6: a_ijk <- a_ijk + phi_a(f_a).

    Reference: f_a = [v^{t+1}, e^{t+1}, a^t]; fast: f_a = [v^t, e^t, a^t].
    """
    center = graph.bond_center[graph.angle_ij]
    f_a = jnp.concatenate(
        [v_in[center], e_in[graph.angle_ij], e_in[graph.angle_ik], a], axis=-1
    )
    upd = gated_mlp_apply(p["angle_mlp"], f_a, mlp_impl)
    return a + upd * graph.angle_mask[..., None].astype(a.dtype)


# ---------------------------------------------------------------------------
# Symmetric half-graph trunk (DESIGN.md §10, bond_features="undirected")
# ---------------------------------------------------------------------------

def _sym_inputs(graph: CrystalGraphBatch, v_in, e_in, a_u):
    """Swap-symmetrized f over Au rows: [v_center, e_s, e_s, a_u].

    e_s = e[du1] + e[du2] is invariant under swapping the pair's two
    bonds, so both directed orientations of a dedup angle produce the
    SAME feature row — the single GatedMLP evaluation stands in for
    both.  Param shapes match the directed f = [v, e_ij, e_ik, a]
    exactly (checkpoint compatible).
    """
    ctr = graph.bond_center[graph.und_angle_ij]
    du1 = graph.bond_pair[graph.und_angle_ij]
    du2 = graph.bond_pair[graph.und_angle_ik]
    e_s = e_in[du1] + e_in[du2]
    f = jnp.concatenate([v_in[ctr], e_s, e_s, a_u], axis=-1)
    return f, du1, du2


def sym_bond_conv(p, graph: CrystalGraphBatch, v_in, e, a_u, e_b, *,
                  mlp_impl, agg_impl, conv_impl: str = "unfused",
                  table_residency: str = "auto"):
    """Symmetrized Eq. 5 over Eu rows (DESIGN.md §10).

    ``e``/``e_b`` live at Eu, ``a_u`` at Au == A/2.  One message per real
    dedup angle w — phi([v_ctr, e_s, e_s, a_u]) * e^b[du1] * e^b[du2],
    swap-invariant by construction — scatters into BOTH undirected bonds
    of the pair through the dest-sorted incidence store
    (sym_dest/sym_rep/sym_offsets), replacing the A-row directed
    bond_conv with Au GatedMLP rows + Eu output rows.

    ``conv_impl="fused"`` routes through the two-launch §10 megakernel
    (Au-tiled message pass + Eu destination-tiled accumulation);
    unfused composes the impl matrix like ``bond_conv``.
    """
    if conv_impl == "fused":
        from repro.kernels import ops as kops  # lazy: avoid import cycle

        mlp = jax.tree.map(lambda t: t.astype(e.dtype), p["bond_mlp"])
        ctr = graph.bond_center[graph.und_angle_ij]
        du1 = graph.bond_pair[graph.und_angle_ij]
        du2 = graph.bond_pair[graph.und_angle_ik]
        agg = kops.fused_sym_bond_conv(
            v_in, e, a_u, e_b, mlp["w"], mlp["b"], mlp["ln_scale"],
            mlp["ln_bias"], ctr, du1, du2, graph.sym_rep, graph.sym_dest,
            graph.sym_offsets, table_residency=table_residency,
        )
    elif conv_impl == "unfused":
        f, du1, du2 = _sym_inputs(graph, v_in, e, a_u)
        msg = gated_mlp_apply(p["bond_mlp"], f, mlp_impl)
        msg = msg * e_b[du1] * e_b[du2]
        # position-based incidence validity: padded incidences carry rep=0,
        # which aliases a REAL Au row, so und_angle_mask[sym_rep] would
        # leak padded contributions
        incid_mask = (
            jnp.arange(graph.angle_cap) < graph.sym_offsets[-1]
        ).astype(e.dtype)
        agg = segment_aggregate(
            msg[graph.sym_rep], graph.sym_dest, graph.und_cap, incid_mask,
            agg_impl, offsets=graph.sym_offsets,
            table_residency=table_residency,
        )
    else:
        raise ValueError(f"unknown conv impl {conv_impl!r}")
    mask = graph.und_mask[..., None].astype(e.dtype)
    return e + linear_apply(p["bond_out"], agg) * mask


def sym_angle_update(p, graph: CrystalGraphBatch, v_in, e_in, a_u, *,
                     mlp_impl):
    """Symmetrized Eq. 6 at Au rows (DESIGN.md §10).

    The swap-symmetrized f_a makes both directed orientations of a dedup
    angle agree, so the single Au-row update stands in for both — the
    remaining angle-level GEMMs run at Au == A/2.  ``e_in`` is the
    Eu-resident bond table.
    """
    f_a, _, _ = _sym_inputs(graph, v_in, e_in, a_u)
    upd = gated_mlp_apply(p["angle_mlp"], f_a, mlp_impl)
    return a_u + upd * graph.und_angle_mask[..., None].astype(a_u.dtype)


def interaction_block_apply(
    p,
    graph: CrystalGraphBatch,
    v,
    e,
    a,
    e_a,
    e_b,
    *,
    variant: str = "fast",
    mlp_impl: str = "packed",
    agg_impl: str = "scatter",
    conv_impl: str = "unfused",
    bond_store: str = "directed",
    bond_features: str = "directed",
    table_residency: str = "auto",
    update_angles: bool = True,
):
    """One interaction block IB^t (paper Eq. 3), either variant.

    ``bond_features="undirected"`` (DESIGN.md §10) swaps in the
    symmetric-trunk updates: ``e`` is Eu-resident, ``a`` is Au-resident,
    and bond_conv/angle_update run their symmetrized forms.
    """
    sym = bond_features == "undirected"
    with jax.named_scope("atom_conv"):
        v_new = atom_conv(p, graph, v, e, e_a, mlp_impl=mlp_impl,
                          agg_impl=agg_impl, conv_impl=conv_impl,
                          bond_store=bond_store, bond_features=bond_features,
                          table_residency=table_residency)

    def _bond(v_in):
        if sym:
            with jax.named_scope("sym_bond_conv"):
                return sym_bond_conv(
                    p, graph, v_in, e, a, e_b, mlp_impl=mlp_impl,
                    agg_impl=agg_impl, conv_impl=conv_impl,
                    table_residency=table_residency,
                )
        with jax.named_scope("bond_conv"):
            return bond_conv(
                p, graph, v_in, e, a, e_b, mlp_impl=mlp_impl,
                agg_impl=agg_impl, conv_impl=conv_impl,
                bond_store=bond_store, table_residency=table_residency,
            )

    def _angle(v_in, e_in):
        if not update_angles:
            return a
        if sym:
            with jax.named_scope("sym_angle_update"):
                return sym_angle_update(p, graph, v_in, e_in, a,
                                        mlp_impl=mlp_impl)
        with jax.named_scope("angle_update"):
            return angle_update(p, graph, v_in, e_in, a, mlp_impl=mlp_impl)

    if variant == "reference":
        e_new = _bond(v_new)
        a_new = _angle(v_new, e_new)
    elif variant == "fast":
        # Dependency elimination (Eq. 11): all three read layer-t features.
        e_new = _bond(v)
        a_new = _angle(v, e)
    else:
        raise ValueError(f"unknown block variant {variant!r}")
    return v_new, e_new, a_new

"""Plain float32 CHGNet: the reference that decides ``correct``.

Written from the model's equations (FastCHGNet paper §II-B and §III, after
CHGNet v0.3.0) in straightforward ``jax.numpy``, with no kernel, no packed
GEMM, no padding policy and nothing imported from the program.  It reads
the benchmark's own weights (a dict in the layout of
``benchlib.weights``) and the benchmark's own structures and graphs
(``benchlib.crystals``).  Every matmul runs at ``highest`` precision, so
on the chip the reference is float32 throughout.

Model, per interaction block t (the "fast" variant: bond and angle updates
read the layer-t features):

    v_i  += L_v[ sum_j e^a_ij * phi_v(v_i, v_j, e_ij) ]
    e_ij += L_e[ sum_k e^b_ij e^b_ik * phi_e(v_i, e_ij, e_ik, a_ijk) ]
    a_ijk += phi_a(v_i, e_ij, e_ik, a_ijk)

with phi(x) = silu(LN(x Wc + bc)) * sigmoid(LN(x Wg + bg)), then a last
atom update.  Readouts: energy (site MLP, summed), magmom (|MLP|), and
either the direct heads (F_i = sum_j n_ij x_hat_ij; stress from an atom
MLP times the lattice-normal matrix) or forces = -dE/dx and stress =
dE/d(strain) / V.

The loss is CHGNet's Huber loss over energy per atom, forces, stress and
magmoms; the optimizer step is global-norm clipping then Adam at a cosine
learning rate, as the paper's recipe states.
"""
from __future__ import annotations

import dataclasses
import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

EV_A3_TO_GPA = 160.21766


@dataclasses.dataclass(frozen=True)
class ModelSpec:
    """The widths and readout of a configuration (``configs/*.json``)."""

    dim: int
    num_rbf: int
    num_fourier: int
    num_blocks: int
    r_cut_atom: float
    r_cut_bond: float
    envelope_p: int
    readout: str  # "direct" | "autodiff"
    # matmul operands rounded as ``_dot`` says (the controls, one step
    # below what a configuration states); None keeps them float32
    operands: str | None = None

    @classmethod
    def from_config(cls, cfg: dict, operands: str | None = None):
        names = [f.name for f in dataclasses.fields(cls)][:-1]
        return cls(**{n: cfg[n] for n in names}, operands=operands)


@dataclasses.dataclass(frozen=True)
class LossSpec:
    energy: float
    force: float
    stress: float
    magmom: float
    huber_delta: float


def _round_up(n: int, m: int) -> int:
    return max(m, -(-n // m) * m)


def flat_graph(structures, graphs, *, num_slots: int, align: int = 4096):
    """Concatenate structures and their graphs into one padded batch.

    Padding (to a multiple of ``align`` rows, so that the reference's
    compiled programs repeat across seeds) is masked out of every sum.
    """
    n_at = [s.num_atoms for s in structures]
    a_off = np.concatenate([[0], np.cumsum(n_at)])
    b_off = np.concatenate([[0], np.cumsum([g.num_bonds for g in graphs])])
    na = _round_up(int(a_off[-1]), align)
    nb = _round_up(int(b_off[-1]), align)
    ng = _round_up(sum(g.num_angles for g in graphs), align)

    def pad(x, n, dtype):
        out = np.zeros((n,) + x.shape[1:], dtype)
        out[:len(x)] = x
        return out

    cat = np.concatenate
    idx = range(len(structures))
    atom_crystal = cat([np.full(n, i) for i, n in zip(idx, n_at)])
    lat = np.tile(np.eye(3), (num_slots, 1, 1))
    lat[:len(structures)] = [s.lattice for s in structures]
    out = {
        "z": pad(cat([s.atomic_numbers for s in structures]), na, np.int32),
        "frac": pad(cat([s.frac_coords for s in structures]), na, np.float32),
        "atom_crystal": pad(atom_crystal, na, np.int32),
        "atom_mask": pad(np.ones(int(a_off[-1])), na, np.float32),
        "lattice": lat.astype(np.float32),
        "crystal_mask": pad(np.ones(len(structures)), num_slots, np.float32),
        "n_atoms": pad(np.asarray(n_at), num_slots, np.float32),
        "center": pad(cat([g.center + a_off[i]
                           for i, g in zip(idx, graphs)]), nb, np.int32),
        "nbr": pad(cat([g.nbr + a_off[i] for i, g in zip(idx, graphs)]),
                   nb, np.int32),
        "image": pad(cat([g.image for g in graphs]), nb, np.float32),
        "bond_crystal": pad(cat([np.full(g.num_bonds, i)
                                 for i, g in zip(idx, graphs)]), nb, np.int32),
        "bond_mask": pad(np.ones(int(b_off[-1])), nb, np.float32),
        "angle_ij": pad(cat([g.angle_ij + b_off[i]
                             for i, g in zip(idx, graphs)]), ng, np.int32),
        "angle_ik": pad(cat([g.angle_ik + b_off[i]
                             for i, g in zip(idx, graphs)]), ng, np.int32),
        "angle_mask": pad(np.ones(sum(g.num_angles for g in graphs)), ng,
                          np.float32),
    }
    if structures[0].energy is not None:
        out["energy"] = pad(np.asarray([s.energy for s in structures]),
                            num_slots, np.float32)
        out["forces"] = pad(cat([s.forces for s in structures]), na,
                            np.float32)
        out["stress"] = pad(np.stack([s.stress for s in structures]),
                            num_slots, np.float32)
        out["magmoms"] = pad(cat([s.magmoms for s in structures]), na,
                             np.float32)
    return out


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------

def _round(t, dtype):
    """``t`` rounded to ``dtype``, as float32.  bfloat16 goes through
    ``lax.reduce_precision``: XLA may drop a float32 -> bfloat16 -> float32
    round trip as excess precision, and on the TPU it does so in
    ``t - round(t)``, which leaves a three-pass product one pass."""
    if jnp.dtype(dtype) == jnp.bfloat16:
        return jax.lax.reduce_precision(t, exponent_bits=8, mantissa_bits=7)
    return t.astype(dtype).astype(jnp.float32)


def _three_pass(op, a, b):
    """``op(a, b)``, a bilinear product, at the TPU's ``high`` precision:
    each operand split into two bf16 parts, the low-low product left out."""
    ah, bh = _round(a, jnp.bfloat16), _round(b, jnp.bfloat16)
    al, bl = _round(a - ah, jnp.bfloat16), _round(b - bh, jnp.bfloat16)
    return op(ah, bh) + op(ah, bl) + op(al, bh)


def _matmul(a, b, q):
    """a @ b with the operands rounded as ``q`` says: a dtype name, or
    "bf16x3" for three bf16 passes (``_three_pass``)."""
    if q == "bf16x3":
        return _three_pass(jnp.matmul, a, b)
    return _round(a, q) @ _round(b, q)


@partial(jax.custom_vjp, nondiff_argnums=(2,))
def _rounded_dot(x, w, q):
    return _matmul(x, w, q)


def _rounded_dot_fwd(x, w, q):
    return _matmul(x, w, q), (x, w)


def _rounded_dot_bwd(q, res, ct):
    # the backward's matmuls at the same precision, as the chip runs them
    x, w = res
    return _matmul(ct, w.T, q), _matmul(x.T, ct, q)


_rounded_dot.defvjp(_rounded_dot_fwd, _rounded_dot_bwd)


def _dot(x, w, q):
    """x @ w at float32, or at the lower precision ``q`` (see
    ``_matmul``) in the forward and the backward alike."""
    return x @ w if q is None else _rounded_dot(x, w, q)


def _linear(p, x, q=None):
    return _dot(x, p["w"], q) + p["b"]


def _layer_norm(x, scale, bias):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + 1e-5) * scale + bias


def _gated(p, x, q):
    d = p["w"].shape[1] // 2
    core = _layer_norm(_dot(x, p["w"][:, :d], q) + p["b"][:d],
                       p["ln_scale"][:d], p["ln_bias"][:d])
    gate = _layer_norm(_dot(x, p["w"][:, d:], q) + p["b"][d:],
                       p["ln_scale"][d:], p["ln_bias"][d:])
    return jax.nn.silu(core) * jax.nn.sigmoid(gate)


def _mlp(layers, x, q):
    for i, p in enumerate(layers):
        x = _linear(p, x, q)
        if i < len(layers) - 1:
            x = jax.nn.silu(x)
    return x


def _segsum(x, ids, n):
    return jax.ops.segment_sum(x, ids, num_segments=n)


def _geometry(g, disp, strain, q):
    # positions are matmuls too: at ``high`` (q "bf16x3") they take three
    # bf16 passes, as ``jax.default_matmul_precision`` gives the program's;
    # a lower operand type ``q`` quantizes the layers alone
    def mm(spec, a, b):
        op = partial(jnp.einsum, spec)
        return _three_pass(op, a, b) if q == "bf16x3" else op(a, b)

    lat = g["lattice"]
    if strain is not None:
        lat = mm("bij,bjk->bik", lat, jnp.eye(3) + strain)
    cart = mm("ai,aij->aj", g["frac"], lat[g["atom_crystal"]])
    if disp is not None:
        cart = cart + disp
    shift = mm("bi,bij->bj", g["image"], lat[g["bond_crystal"]])
    vec = cart[g["nbr"]] + shift - cart[g["center"]]
    dist = jnp.sqrt(jnp.sum(vec * vec, axis=-1) + 1e-16)
    v1, v2 = vec[g["angle_ij"]], vec[g["angle_ik"]]
    cos = jnp.sum(v1 * v2, -1) / (dist[g["angle_ij"]] * dist[g["angle_ik"]]
                                  + 1e-12)
    theta = jnp.arccos(jnp.clip(cos, -1.0 + 1e-7, 1.0 - 1e-7))
    return vec, dist, theta


def _rbf(dist, freqs, r_cut, p):
    xi = dist / r_cut
    env = (1.0 - (p + 1) * (p + 2) / 2.0 * xi ** p + p * (p + 2) * xi ** (p + 1)
           - p * (p + 1) / 2.0 * xi ** (p + 2))
    r = jnp.where(dist > 1e-8, dist, 1.0)
    return (math.sqrt(2.0 / r_cut) * jnp.sin(xi[:, None] * freqs)
            / r[:, None] * env[:, None])


def _fourier(theta, n_basis):
    n = jnp.arange(1, (n_basis - 1) // 2 + 1, dtype=jnp.float32)
    ang = theta[:, None] * n
    dc = jnp.full(theta.shape + (1,), 1.0 / math.sqrt(2.0))
    return jnp.concatenate([dc, jnp.cos(ang), jnp.sin(ang)], -1) \
        / math.sqrt(math.pi)


def _atom_update(p, g, v, e, e_a, q):
    c, n = g["center"], g["nbr"]
    msg = _gated(p["atom_mlp"], jnp.concatenate([v[c], v[n], e], -1), q) * e_a
    agg = _segsum(msg * g["bond_mask"][:, None], c, v.shape[0])
    return v + _linear(p["atom_out"], agg, q) * g["atom_mask"][:, None]


def _trunk(params, spec: ModelSpec, g, disp=None, strain=None):
    q = spec.operands
    vec, dist, theta = _geometry(g, disp, strain, q)
    rbf = _rbf(dist, params["rbf_freqs"], spec.r_cut_atom, spec.envelope_p)
    e0, e_a, e_b = jnp.split(_linear(params["bond_embed"], rbf, q), 3,
                             axis=-1)
    bmask = g["bond_mask"][:, None]
    gmask = g["angle_mask"][:, None]
    v = params["atom_embed"][g["z"]] * g["atom_mask"][:, None]
    e = e0 * bmask
    a = _linear(params["angle_embed"],
                _fourier(theta, spec.num_fourier), q) * gmask
    ij, ik = g["angle_ij"], g["angle_ik"]
    ctr = g["center"][ij]
    for blk in params["blocks"]:
        f = jnp.concatenate([v[ctr], e[ij], e[ik], a], -1)
        msg = _gated(blk["bond_mlp"], f, q) * e_b[ij] * e_b[ik]
        agg = _segsum(msg * gmask, ij, e.shape[0])
        e_new = e + _linear(blk["bond_out"], agg, q) * bmask
        a = a + _gated(blk["angle_mlp"], f, q) * gmask
        v = _atom_update(blk, g, v, e, e_a, q)
        e = e_new
    v = _atom_update(params["final_block"], g, v, e, e_a, q)
    return v, e, vec, dist


def _energy(params, g, v, q):
    site = _mlp(params["energy_head"]["mlp"], v, q)[:, 0] * g["atom_mask"]
    return _segsum(site, g["atom_crystal"], g["lattice"].shape[0])


def apply(params, spec: ModelSpec, g) -> dict:
    """Energy (B,), forces (A, 3), stress (B, 3, 3) GPa, magmom (A,)."""
    nb, q = g["lattice"].shape[0], spec.operands
    if spec.readout == "direct":
        v, e, vec, dist = _trunk(params, spec, g)
        n_ij = _mlp(params["force_head"]["mlp"], e, q)[:, 0]
        x_hat = vec / (dist[:, None] + 1e-12)
        forces = _segsum(n_ij[:, None] * x_hat * g["bond_mask"][:, None],
                         g["center"], v.shape[0]) * g["atom_mask"][:, None]
        lat = g["lattice"]
        s = jnp.sum(lat / (jnp.linalg.norm(lat, axis=-1, keepdims=True)
                           + 1e-12), axis=1)
        per_atom = _mlp(params["stress_head"]["mlp"], v, q) \
            * g["atom_mask"][:, None]
        stress = (params["stress_head"]["scale"]
                  * _segsum(per_atom, g["atom_crystal"], nb).reshape(-1, 3, 3)
                  * s[:, :, None] * s[:, None, :])
    else:
        def total(disp, strain):
            v = _trunk(params, spec, g, disp, strain)[0]
            return jnp.sum(_energy(params, g, v, q)), v

        zeros_x = jnp.zeros_like(g["frac"])
        zeros_s = jnp.zeros_like(g["lattice"])
        (d_x, d_s), v = jax.grad(total, (0, 1), has_aux=True)(zeros_x,
                                                               zeros_s)
        forces = -d_x * g["atom_mask"][:, None]
        vol = jnp.abs(jnp.linalg.det(g["lattice"]))
        stress = (d_s / (vol[:, None, None] + 1e-12) * EV_A3_TO_GPA
                  * g["crystal_mask"][:, None, None])
    magmom = jnp.abs(_mlp(params["magmom_head"]["mlp"], v, q)[:, 0]) \
        * g["atom_mask"]
    return {"energy": _energy(params, g, v, q), "forces": forces,
            "stress": stress, "magmom": magmom}


# ---------------------------------------------------------------------------
# Loss and optimizer step
# ---------------------------------------------------------------------------

def _huber(x, delta):
    ax = jnp.abs(x)
    return jnp.where(ax <= delta, 0.5 * x * x, delta * (ax - 0.5 * delta))


def denominators(structure_counts: list[int]) -> dict:
    """Loss denominators of a whole optimizer step: crystals, 3 x atoms,
    9 x crystals, atoms (each term is a mean over its real entries)."""
    c, a = float(len(structure_counts)), float(sum(structure_counts))
    return {"energy": c, "force": 3.0 * a, "stress": 9.0 * c, "magmom": a}


def loss_part(params, spec: ModelSpec, w: LossSpec, g, denoms) -> jnp.ndarray:
    """This block's share of the step's loss (blocks of one step add up)."""
    out = apply(params, spec, g)
    cm, am = g["crystal_mask"], g["atom_mask"]
    e_err = (out["energy"] - g["energy"]) / jnp.maximum(g["n_atoms"], 1.0)
    terms = (
        (w.energy, jnp.sum(_huber(e_err, w.huber_delta) * cm),
         denoms["energy"]),
        (w.force, jnp.sum(_huber(out["forces"] - g["forces"], w.huber_delta)
                          * am[:, None]), denoms["force"]),
        (w.stress, jnp.sum(_huber(out["stress"] - g["stress"], w.huber_delta)
                           * cm[:, None, None]), denoms["stress"]),
        (w.magmom, jnp.sum(_huber(out["magmom"] - g["magmoms"],
                                  w.huber_delta) * am), denoms["magmom"]),
    )
    return sum(k * s / d for k, s, d in terms)


@dataclasses.dataclass(frozen=True)
class OptSpec:
    init_lr: float
    total_steps: int
    grad_clip: float = 1.0
    b1: float = 0.9
    b2: float = 0.999
    eps: float = 1e-8


def learning_rate(opt: OptSpec, step: int) -> float:
    prog = min(max(step / max(opt.total_steps, 1), 0.0), 1.0)
    return opt.init_lr * 0.5 * (1.0 + math.cos(math.pi * prog))


def clip(grads, max_norm: float):
    leaves = jax.tree.leaves(grads)
    norm = jnp.sqrt(sum(jnp.sum(x * x) for x in leaves))
    scale = jnp.minimum(1.0, max_norm / (norm + 1e-12))
    return jax.tree.map(lambda x: x * scale, grads)


def adam_step(opt: OptSpec, params, mu, nu, grads, count: int, lr: float):
    mu = jax.tree.map(lambda m, g: opt.b1 * m + (1 - opt.b1) * g, mu, grads)
    nu = jax.tree.map(lambda v, g: opt.b2 * v + (1 - opt.b2) * g * g, nu,
                      grads)
    bc1, bc2 = 1.0 - opt.b1 ** count, 1.0 - opt.b2 ** count
    params = jax.tree.map(
        lambda p, m, v: p - lr * (m / bc1) / (jnp.sqrt(v / bc2) + opt.eps),
        params, mu, nu)
    return params, mu, nu


class Trainer:
    """Plain training: for each step, the gradient of the step's loss is
    summed over blocks of structures, clipped, and applied by Adam."""

    def __init__(self, spec: ModelSpec, loss: LossSpec, opt: OptSpec):
        self.spec, self.loss, self.opt = spec, loss, opt
        self._grad = jax.jit(jax.value_and_grad(
            lambda p, g, d: loss_part(p, spec, loss, g, d)))

    def step(self, params, mu, nu, blocks: list[dict], denoms: dict,
             step: int):
        """One optimizer step over ``blocks`` (flat graphs of one global
        batch).  Returns (params, mu, nu, loss, clipped grads)."""
        with jax.default_matmul_precision("highest"):
            loss, grads = 0.0, None
            for g in blocks:
                lv, gr = self._grad(params, g, denoms)
                loss = loss + lv
                grads = gr if grads is None else jax.tree.map(jnp.add,
                                                              grads, gr)
            grads = clip(grads, self.opt.grad_clip)
            params, mu, nu = adam_step(self.opt, params, mu, nu, grads,
                                       step + 1,
                                       learning_rate(self.opt, step))
        return params, mu, nu, float(loss), grads


def predict(params, spec: ModelSpec, g) -> dict:
    """Reference outputs for one flat graph, as host arrays."""
    with jax.default_matmul_precision("highest"):
        out = _apply_jit(params, spec, g)
        return jax.device_get(out)


_apply_jit = jax.jit(apply, static_argnums=1)

"""Faults planted under the timed path, and the lower-precision control.

The comparison that decides ``correct`` has to fail each of these.  They
are planted on the program's live objects (never in its files) through
the drivers' ``ctx.plant(kind, obj)`` hook: ``kind`` is "trainer" for a
``Trainer`` and "md" for a ``BatchedMD``.  ``bench/readings.py`` reads
them on the chip; ``bench/tests`` checks on the CPU that each one turns
``correct`` false.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

def control(config: dict, backend: str) -> dict:
    """The control of a configuration: one precision step below what it
    states.

    - float32 at JAX's default precision, which on the TPU is one bf16
      pass per matmul (bf16 operands, float32 accumulation): the reference
      with its matmul operands rounded to float8 (e4m3), forward and
      backward, in the program's place.
    - float32 at ``highest``: the program itself at ``high`` (three bf16
      passes).  A backend that ignores the matmul precision, as the CPU
      does, gets the reference at ``high`` instead, emulated operand by
      operand, in the program's place.
    """
    if config["matmul_precision"] == "default":
        return {"reference_operands": "float8_e4m3fn"}
    if config["matmul_precision"] == "highest":
        if backend == "tpu":
            return {"matmul_precision": "high"}
        return {"reference_operands": "bf16x3"}
    raise ValueError(f"no control for {config['matmul_precision']!r}")


def _half_batch(batch):
    """The second half of the real crystals masked out (on every device's
    shard of a stacked batch): the loss covers the first half alone."""
    a = jnp.asarray
    cmask = a(batch.crystal_mask)
    half = jnp.floor(jnp.sum(cmask, -1, keepdims=True) / 2)
    slots = jnp.arange(cmask.shape[-1])
    bond_mask = a(batch.bond_mask) * (a(batch.bond_crystal) < half)
    return dataclasses.replace(
        batch,
        atom_mask=a(batch.atom_mask) * (a(batch.atom_crystal) < half),
        crystal_mask=cmask * (slots < half),
        bond_mask=bond_mask,
        angle_mask=a(batch.angle_mask) * jnp.take_along_axis(
            bond_mask, a(batch.angle_ij), axis=-1))


def _unchanged(step):
    def wrapped(params, opt_state, batch, i):
        keep = jax.tree.map(jnp.copy, (params, opt_state))
        _, _, metrics = step(params, opt_state, batch, i)
        return keep[0], keep[1], metrics
    return wrapped


def _local_grads(tr):
    """The accumulation path's gradient step with the exchange between
    chips left out: each chip keeps its own shard's gradient and sums."""
    from jax.sharding import PartitionSpec as P

    from repro.core.chgnet import chgnet_apply
    from repro.core.losses import chgnet_loss_sums

    cfg, loss_w = tr.model_cfg, tr.train_cfg.loss

    def local(params, batch, denoms, scale):
        b = jax.tree.map(lambda x: x[0], batch)

        def loss_fn(p):
            loss, sums = chgnet_loss_sums(chgnet_apply(p, cfg, b), b, loss_w,
                                          denoms)
            return loss * scale, sums

        (_, sums), grads = jax.value_and_grad(loss_fn, has_aux=True)(params)
        return grads, sums

    return jax.jit(jax.shard_map(local, mesh=tr.mesh,
                                 in_specs=(P(), P("data"), P(), P()),
                                 out_specs=(P(), P()), check_vma=False))


def _plant_accum(tr, fault: str) -> None:
    """Faults on the gradient-accumulation path (cost-balanced steps)."""
    grad_step, apply_step = tr._get_accum_fns()
    if fault == "half_batch":
        def grad(params, batch, denoms, scale):
            rest = {k: v * 0.5 for k, v in denoms.items()}
            return grad_step(params, _half_batch(batch), rest, scale)
        tr._accum_fns = (grad, apply_step)
    elif fault == "unchanged":
        def apply(params, opt_state, *rest):
            keep = jax.tree.map(jnp.copy, (params, opt_state))
            _, _, metrics = apply_step(params, opt_state, *rest)
            return keep[0], keep[1], metrics
        tr._accum_fns = (grad_step, apply)
    elif fault == "no_exchange":
        tr._accum_fns = (_local_grads(tr), apply_step)


def plant(fault: str | None):
    """A ``ctx.plant`` hook that plants ``fault`` (None: nothing)."""

    def hook(kind: str, obj) -> None:
        if fault is None:
            return
        if kind == "trainer" and fault in ("half_batch", "unchanged",
                                           "no_exchange"):
            # both paths: the plain step and the accumulation steps
            _plant_accum(obj, fault)
        if kind == "trainer" and fault == "half_batch":
            step = obj._train_step
            obj._train_step = lambda p, o, b, i: step(p, o, _half_batch(b),
                                                      i)
        elif kind == "trainer" and fault == "unchanged":
            obj._train_step = _unchanged(obj._train_step)
        elif kind == "md" and fault == "altered_force":
            serve = obj.serve
            step_fn = serve.step_fn

            def altered(*a, **k):
                fn = step_fn(*a, **k)

                def call(params, batch):
                    out = fn(params, batch)
                    return dict(out, forces=out["forces"].at[0, 0].add(1.0))
                return call
            serve.step_fn = altered
        elif kind == "md" and fault == "unchanged":
            step = obj.step

            def frozen(n=1):
                before = [r.crystal.frac_coords.copy() for r in obj.replicas]
                out = step(n)
                for r, f in zip(obj.replicas, before):
                    r.crystal.frac_coords = f
                return out
            obj.step = frozen
    return hook

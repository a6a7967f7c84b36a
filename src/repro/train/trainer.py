"""Training-step builders and the Trainer loop (paper §III-C, §V-B/C).

``make_chgnet_step_fns`` builds jitted train/eval/serve steps for any
CHGNetConfig — both readout modes, so the Fig. 8 "decoupling" speedup and
the second-order-derivative cost are directly measurable.

``make_dp_train_step`` wraps the loss in shard_map data parallelism over a
mesh axis: per-device graph shards (leading axis), gradient all-reduce via
plain / bucketed / bf16-compressed psum (paper C8 + beyond-paper
compression), replicated Adam update.

Mixed precision (DESIGN.md §4): when ``CHGNetConfig.precision`` computes
below f32, the train steps scale the loss (``TrainConfig.loss_scale``),
unscale-to-f32 BEFORE clipping, skip the update on inf/nan grads (and
halve the dynamic scale), and keep f32 master weights via ``optim.adam``.
Scaler state lives INSIDE ``opt_state`` (``opt_state["loss_scale"]``), so
step signatures, the compile cache, the DP path, and checkpoints are
unchanged; metrics gain ``loss_scale`` / ``grads_finite`` entries.  The
same applies on the DP path: the psum reduces *scaled* grads (composing
with the bf16-compressed collective), and unscale/skip runs replicated
after the all-reduce so every device takes the same decision.
"""
from __future__ import annotations

import dataclasses
import time
from functools import partial
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.batching import CompileCache, global_compile_cache
from repro.batching.balance import StepPlan
from repro.core.chgnet import CHGNetConfig, chgnet_apply, chgnet_init
from repro.core.graph import CrystalGraphBatch
from repro.core.losses import (
    LossWeights,
    chgnet_loss,
    chgnet_loss_sums,
    metrics_from_sums,
)
from repro.distributed.collectives import bucketed_psum, compressed_psum
from repro.optim.adam import AdamConfig, adam_init, adam_update
from repro.optim.grad import (
    clip_by_global_norm,
    tree_all_finite,
    unscale_grads,
)
from repro.optim.schedule import cosine_annealing, scaled_init_lr
from repro.precision import (
    LossScaleConfig,
    cast_float_tree,
    loss_scale_init,
    loss_scale_update,
    resolve_policy,
    scale_loss,
)
from repro.runtime import spans


@dataclasses.dataclass(frozen=True)
class TrainConfig:
    global_batch: int = 128
    total_steps: int = 1000
    warmup_steps: int = 0
    lr_k: int = 128                # Eq. 14 divisor
    base_lr: float = 3e-4
    grad_clip: float = 1.0
    grad_reduce: str = "bucketed"  # "plain" | "bucketed" | "compressed"
    adam: AdamConfig = AdamConfig()
    loss: LossWeights = LossWeights()
    # loss scaling (DESIGN.md §4): "auto" enables the dynamic scaler iff
    # the model policy computes below f32, so the f32 path is unchanged
    loss_scale: LossScaleConfig = LossScaleConfig()
    # live cost-model refits (DESIGN.md §6): every K optimizer steps the
    # Trainer refits batching/cost.fit_cost_model from measured
    # per-microbatch wall times (block_until_ready per micro — only paid
    # when enabled) and hands the result to ``Trainer.on_cost_model``
    # (the launcher wires that to BalancedBatchIterator.update_cost_model,
    # closing the predict -> pack -> measure -> refit loop).  0 = off.
    cost_refit_every: int = 0
    # optimizer steps to discard before sampling (compile-inflated timings
    # would otherwise dominate the fit) and the bounded sample window
    cost_refit_warmup: int = 2
    cost_refit_window: int = 256
    # divergence rollback (DESIGN.md §8): when on, a streak of non-finite
    # losses (divergence_nan_streak) or of losses > divergence_spike_factor
    # x the trailing-median (divergence_spike_streak over a
    # divergence_window history) restores the newest VALID checkpoint,
    # multiplies the LR by rollback_lr_factor (cumulative, rides in
    # ``opt_state["lr_scale"]`` so it checkpoints; 1.0 = keep LR), and
    # quarantines the streak's batch indices via ``Trainer.on_quarantine``.
    # Scaler-skipped steps (§4 overflow rejections) never count.  Off by
    # default: the legacy single-NaN restore-or-raise guard applies.
    rollback_on_divergence: bool = False
    divergence_nan_streak: int = 2
    divergence_spike_factor: float = 10.0
    divergence_spike_streak: int = 4
    divergence_window: int = 32
    rollback_lr_factor: float = 0.5
    max_rollbacks: int = 8

    @property
    def init_lr(self) -> float:
        return scaled_init_lr(self.global_batch, self.lr_k, self.base_lr)


def chgnet_loss_fn(params, cfg: CHGNetConfig, batch: CrystalGraphBatch,
                   weights: LossWeights):
    pred = chgnet_apply(params, cfg, batch)
    return chgnet_loss(pred, batch, weights)


def _scaled_chgnet_loss_fn(params, cfg, batch, weights, scaler):
    """Loss for value_and_grad, multiplied by the (optional) loss scale.
    Metrics carry the UNSCALED loss."""
    loss, metrics = chgnet_loss_fn(params, cfg, batch, weights)
    if scaler is not None:
        loss = scale_loss(loss, scaler)
    return loss, metrics


@jax.named_scope("optimizer")
def _apply_grads(grads, opt_state, params, lr, train_cfg: TrainConfig,
                 scale_kind: str):
    """Shared tail of every train step: (optionally) unscale -> clip ->
    Adam -> skip-on-nonfinite -> scaler update (DESIGN.md §4), all under
    the device scope ``optimizer``.

    ``opt_state`` may carry a ``"loss_scale"`` subtree; its presence (a
    trace-time structure property) turns on the scaled path.  An
    ``opt_state["lr_scale"]`` scalar (divergence rollback, DESIGN.md §8)
    multiplies the schedule LR and passes through ``adam_update`` like any
    extra state key.  Returns (params, opt_state, extra_metrics).
    """
    lr_scale = opt_state.get("lr_scale")
    if lr_scale is not None:
        lr = lr * lr_scale
    scaler = opt_state.get("loss_scale")
    if scaler is None:
        grads = clip_by_global_norm(grads, train_cfg.grad_clip)
        params, opt_state = adam_update(grads, opt_state, params, lr,
                                        train_cfg.adam)
        extra = {} if lr_scale is None else {"lr_scale": lr_scale}
        return params, opt_state, extra

    adam_state = {k: v for k, v in opt_state.items() if k != "loss_scale"}
    # unscale to f32 BEFORE clipping so the clip threshold is in true
    # gradient units; the finite check sees the true grads too
    grads = unscale_grads(grads, scaler["scale"])
    finite = tree_all_finite(grads)
    grads = clip_by_global_norm(grads, train_cfg.grad_clip)
    new_params, new_adam = adam_update(grads, adam_state, params, lr,
                                       train_cfg.adam)
    # inf/nan grads: skip the whole update (params, moments, count) …
    keep = lambda new, old: jax.tree.map(
        lambda n, o: jnp.where(finite, n, o), new, old)
    params = keep(new_params, params)
    adam_state = keep(new_adam, adam_state)
    # … and let the scaler back off / grow
    scaler = loss_scale_update(scaler, finite, train_cfg.loss_scale,
                               scale_kind)
    opt_state = dict(adam_state, loss_scale=scaler)
    extra = {"loss_scale": scaler["scale"],
             "grads_finite": finite.astype(jnp.float32)}
    if lr_scale is not None:
        extra["lr_scale"] = lr_scale
    return params, opt_state, extra


# ---------------------------------------------------------------------------
# Single-device steps
# ---------------------------------------------------------------------------

def make_chgnet_step_fns(model_cfg: CHGNetConfig, train_cfg: TrainConfig,
                         *, cache: CompileCache | None = None,
                         donate: bool = True):
    """Returns (train_step, eval_step, serve_step), all jitted.

    With ``cache`` (a ``repro.batching.CompileCache``), the jitted wrappers
    are memoized per ``(kind, model_cfg, train_cfg, donate)`` — a new
    Trainer after a fault restart reuses the already-traced step instead
    of starting from an empty jit cache.  (Per-shape/bucket specialisation
    below the wrapper is jit's own cache; the ladder bounds how many
    shapes exist.)

    ``donate`` (default on): the train step donates ``params``/
    ``opt_state`` and the serve step donates its batch — callers must
    treat those arguments as consumed (the Trainer loop rebinds both every
    step; ``benchmarks/bench_iteration.run_donation_probe`` tracks the
    compiled-memory delta).  Eval donates nothing: eval batches are
    legitimately reused.
    """

    def lr_at(step):
        return cosine_annealing(
            step, train_cfg.total_steps, train_cfg.init_lr,
            warmup_steps=train_cfg.warmup_steps,
        )

    scale_kind = train_cfg.loss_scale.resolved_kind(model_cfg.precision)

    def build_train():
        # donate params/opt_state: the returned trees alias the input
        # buffers instead of allocating fresh copies, so the params +
        # optimizer state never exist twice.  Callers must treat the
        # passed-in params/opt_state as consumed — the Trainer loop
        # rebinds both every step.
        @partial(jax.jit, donate_argnums=(0, 1) if donate else ())
        def train_step(params, opt_state, batch, step):
            scaler = opt_state.get("loss_scale")
            (_, metrics), grads = jax.value_and_grad(
                _scaled_chgnet_loss_fn, has_aux=True
            )(params, model_cfg, batch, train_cfg.loss, scaler)
            params, opt_state, extra = _apply_grads(
                grads, opt_state, params, lr_at(step), train_cfg,
                scale_kind)
            return params, opt_state, dict(metrics, **extra)

        return train_step

    def build_eval():
        @jax.jit
        def eval_step(params, batch):
            _, metrics = chgnet_loss_fn(params, model_cfg, batch,
                                        train_cfg.loss)
            return metrics

        return eval_step

    def build_serve():
        # donate the batch (the serve step's per-call state): each packed
        # batch is consumed exactly once per prediction, so its buffers
        # can back the outputs; params are NOT donated (reused every call)
        @partial(jax.jit, donate_argnums=(1,) if donate else ())
        def serve_step(params, batch):
            """One MD step's worth of inference (Table II)."""
            return chgnet_apply(params, model_cfg, batch)

        return serve_step

    if cache is None:
        return build_train(), build_eval(), build_serve()
    # donate is part of the key: a donated and an undonated step are
    # different executables and must never satisfy each other's lookups
    key = (model_cfg, train_cfg, donate)
    return (
        cache.get(("chgnet_train",) + key, build_train),
        cache.get(("chgnet_eval",) + key, build_eval),
        cache.get(("chgnet_serve",) + key, build_serve),
    )


def make_chgnet_eval_serve_step(model_cfg: CHGNetConfig,
                                train_cfg: TrainConfig,
                                *, cache: CompileCache | None = None,
                                donate: bool = True):
    """One jitted ``(params, batch) -> (metrics, outputs)`` step that runs
    the forward ONCE and derives both the eval metrics and the serve
    outputs from it — callers that want predictions *and* MAEs (validation
    epochs that archive outputs, MD loops that log errors) previously paid
    two forwards and kept two batches resident.

    ``donate`` (default on): the batch is consumed exactly once per call,
    so its buffers may back the outputs (``tests/test_donation.py``
    asserts the aliasing survives compilation); params are NOT donated —
    they are reused every call, matching the serve-step contract.
    """

    def build():
        @partial(jax.jit, donate_argnums=(1,) if donate else ())
        def eval_serve_step(params, batch):
            out = chgnet_apply(params, model_cfg, batch)
            _, metrics = chgnet_loss(out, batch, train_cfg.loss)
            return metrics, out

        return eval_serve_step

    if cache is None:
        return build()
    return cache.get(("chgnet_eval_serve", model_cfg, train_cfg, donate),
                     build)


# ---------------------------------------------------------------------------
# Data-parallel step (shard_map over a mesh axis)
# ---------------------------------------------------------------------------

def make_dp_train_step(model_cfg: CHGNetConfig, train_cfg: TrainConfig,
                       mesh: Mesh, axis: str = "data",
                       *, cache: CompileCache | None = None,
                       donate: bool = True):
    """Train step over per-device graph shards (leading axis = devices).

    batch leaves: (num_devices, ...) sharded P(axis); params replicated.
    ``donate`` mirrors the single-device contract (params/opt_state are
    consumed) and is part of the compile-cache key.
    """
    if cache is not None:
        return cache.get(
            ("chgnet_dp_train", model_cfg, train_cfg, mesh, axis, donate),
            lambda: make_dp_train_step(model_cfg, train_cfg, mesh, axis,
                                       donate=donate),
        )

    def lr_at(step):
        return cosine_annealing(
            step, train_cfg.total_steps, train_cfg.init_lr,
            warmup_steps=train_cfg.warmup_steps,
        )

    scale_kind = train_cfg.loss_scale.resolved_kind(model_cfg.precision)

    def local_step(params, opt_state, batch, step):
        # leading device axis is 1 locally -> squeeze
        local_batch = jax.tree.map(lambda x: x[0], batch)
        scaler = opt_state.get("loss_scale")
        (_, metrics), grads = jax.value_and_grad(
            _scaled_chgnet_loss_fn, has_aux=True
        )(params, model_cfg, local_batch, train_cfg.loss, scaler)
        # the all-reduce sees SCALED grads (composes with the bf16
        # compressed psum: scaling lifts small cotangents above bf16's
        # rounding before quantization); unscale + skip run replicated
        # after it, so every device takes the same decision
        if train_cfg.grad_reduce == "plain":
            grads = jax.lax.psum(grads, axis)
        elif train_cfg.grad_reduce == "bucketed":
            grads = bucketed_psum(grads, axis)
        elif train_cfg.grad_reduce == "compressed":
            grads = compressed_psum(grads, axis)
        else:
            raise ValueError(train_cfg.grad_reduce)
        grads = jax.tree.map(lambda g: g / mesh.shape[axis], grads)
        params, opt_state, extra = _apply_grads(
            grads, opt_state, params, lr_at(step), train_cfg, scale_kind)
        metrics = jax.lax.pmean(metrics, axis)
        return params, opt_state, dict(metrics, **extra)

    batch_spec = P(axis)
    sharded = jax.shard_map(
        local_step,
        mesh=mesh,
        in_specs=(P(), P(), batch_spec, P()),
        out_specs=(P(), P(), P()),
        check_vma=False,
    )
    # donate params/opt_state (same contract as the single-device step)
    return jax.jit(sharded, donate_argnums=(0, 1) if donate else ())


def make_dp_eval_step(model_cfg: CHGNetConfig, train_cfg: TrainConfig,
                      mesh: Mesh, axis: str = "data",
                      *, cache: CompileCache | None = None,
                      donate: bool = False):
    """Replicated-params eval over per-device graph shards -> pmean metrics.

    ``donate`` (default OFF, matching single-device eval: eval batches are
    legitimately reused) consumes the batch — opt in for one-shot eval
    sweeps where every packed batch is fresh.  Note eval outputs are
    scalar metrics, so XLA can never actually *alias* a donated batch
    buffer here — donation only releases the buffers early; the flag
    still rides the compile-cache key so donated/undonated builds never
    collide.
    """
    if cache is not None:
        return cache.get(
            ("chgnet_dp_eval", model_cfg, train_cfg, mesh, axis, donate),
            lambda: make_dp_eval_step(model_cfg, train_cfg, mesh, axis,
                                      donate=donate),
        )

    def local_eval(params, batch):
        local_batch = jax.tree.map(lambda x: x[0], batch)
        _, metrics = chgnet_loss_fn(params, model_cfg, local_batch,
                                    train_cfg.loss)
        return jax.lax.pmean(metrics, axis)

    return jax.jit(jax.shard_map(
        local_eval, mesh=mesh,
        in_specs=(P(), P(axis)), out_specs=P(), check_vma=False,
    ), donate_argnums=(1,) if donate else ())


def make_dp_serve_step(model_cfg: CHGNetConfig, mesh: Mesh,
                       axis: str = "data",
                       *, cache: CompileCache | None = None,
                       donate: bool = True):
    """Replicated-params inference; outputs keep the leading device axis.

    ``donate`` (default on, same contract as single-device serve): each
    packed batch is consumed exactly once per prediction, so its float
    buffers can back the outputs; params are never donated.
    """
    if cache is not None:
        return cache.get(
            ("chgnet_dp_serve", model_cfg, mesh, axis, donate),
            lambda: make_dp_serve_step(model_cfg, mesh, axis,
                                       donate=donate),
        )

    def local_serve(params, batch):
        local_batch = jax.tree.map(lambda x: x[0], batch)
        out = chgnet_apply(params, model_cfg, local_batch)
        return jax.tree.map(lambda x: x[None], out)

    return jax.jit(jax.shard_map(
        local_serve, mesh=mesh,
        in_specs=(P(), P(axis)), out_specs=P(axis), check_vma=False,
    ), donate_argnums=(1,) if donate else ())


# ---------------------------------------------------------------------------
# Gradient accumulation across uneven capacity buckets (DESIGN.md §6)
# ---------------------------------------------------------------------------

def make_chgnet_accum_step_fns(model_cfg: CHGNetConfig,
                               train_cfg: TrainConfig,
                               *, mesh: Mesh | None = None,
                               axis: str = "data",
                               cache: CompileCache | None = None,
                               donate: bool = True):
    """Returns ``(grad_step, apply_step)`` for bucketed accumulation.

    One optimizer step = several microbatches, each packed to its OWN
    (smallest-fitting) capacity bucket by the balancer
    (``repro.batching.balance.plan_microbatches``):

      - ``grad_step(params, batch, denoms, scale) -> (grads, sums)``
        computes the gradient of this microbatch's *partial* loss —
        masked Huber sums over the step-global ``denoms``
        (``losses.global_denominators``) times ``scale`` (the loss-scale
        value, 1.0 on the f32 path).  Because the denominators are
        global, microbatch losses/grads are exactly additive: summing
        them reproduces the single-big-batch gradient bit-for-bit in
        expectation and to ~1e-6 in f32 practice (reassociation only).
        In mesh mode the shard_map psum performs the *device* half of
        that same sum (no ``/num_devices`` — the global denominators
        already normalize), so idle all-padding shards add exact zeros.
      - ``apply_step(params, opt_state, grads, sums, denoms, step)``
        runs the shared update tail (unscale -> clip -> Adam ->
        skip-on-nonfinite -> scaler update).  Skip-on-inf composes across
        microbatches for free: an inf/nan in ANY microbatch poisons the
        accumulated sum, so the one finite-check in ``_apply_grads``
        rejects the whole step and backs the scale off, exactly like a
        single-batch overflow.

    ``donate``: apply_step donates params/opt_state (the Trainer rebinds
    both).  grad_step donates NOTHING: its outputs are param-shaped
    grads plus scalar sums, so no batch buffer could ever back an output
    — donating the batch would only emit unusable-donation warnings.
    """
    if cache is not None:
        key = ("chgnet_accum", model_cfg, train_cfg, mesh, axis, donate)
        return cache.get(key, lambda: make_chgnet_accum_step_fns(
            model_cfg, train_cfg, mesh=mesh, axis=axis, donate=donate))

    def lr_at(step):
        return cosine_annealing(
            step, train_cfg.total_steps, train_cfg.init_lr,
            warmup_steps=train_cfg.warmup_steps,
        )

    scale_kind = train_cfg.loss_scale.resolved_kind(model_cfg.precision)

    def local_grads(params, batch, denoms, scale):
        def loss_fn(p):
            pred = chgnet_apply(p, model_cfg, batch)
            loss, sums = chgnet_loss_sums(pred, batch, train_cfg.loss,
                                          denoms)
            return loss * scale.astype(loss.dtype), sums

        (_, sums), grads = jax.value_and_grad(loss_fn, has_aux=True)(params)
        return grads, sums

    if mesh is None:
        grad_step = jax.jit(local_grads)
    else:
        def local_step(params, batch, denoms, scale):
            local_batch = jax.tree.map(lambda x: x[0], batch)
            grads, sums = local_grads(params, local_batch, denoms, scale)
            # device dimension of the global sum: psum partial grads/sums,
            # NO division — global denominators already normalize, and
            # all-padding shards (devices idled by a small microbatch)
            # contribute exact zeros
            if train_cfg.grad_reduce == "plain":
                grads = jax.lax.psum(grads, axis)
            elif train_cfg.grad_reduce == "bucketed":
                grads = bucketed_psum(grads, axis)
            elif train_cfg.grad_reduce == "compressed":
                grads = compressed_psum(grads, axis)
            else:
                raise ValueError(train_cfg.grad_reduce)
            sums = jax.lax.psum(sums, axis)
            return grads, sums

        grad_step = jax.jit(jax.shard_map(
            local_step, mesh=mesh,
            in_specs=(P(), P(axis), P(), P()),
            out_specs=(P(), P()),
            check_vma=False,
        ))

    # donate params/opt_state only: grads' buffers can't back any output
    # (params/opt_state already alias them all), so donating them would
    # just emit unusable-donation warnings every trace
    @partial(jax.jit, donate_argnums=(0, 1) if donate else ())
    def apply_step(params, opt_state, grads, sums, denoms, step):
        params, opt_state, extra = _apply_grads(
            grads, opt_state, params, lr_at(step), train_cfg, scale_kind)
        metrics = metrics_from_sums(sums, denoms)
        return params, opt_state, dict(metrics, **extra)

    return grad_step, apply_step


def _strip_precision_state(state: dict) -> dict:
    """Trainer-state template minus the policy-dependent leaves
    (``opt_state["loss_scale"]`` / ``opt_state["master"]`` from DESIGN.md
    §4, ``opt_state["lr_scale"]`` from the §8 rollback policy) — the shape
    a checkpoint written under different flags has.  The restore path
    re-grows whichever of them this trainer wants."""
    opt = {k: v for k, v in state["opt_state"].items()
           if k not in ("loss_scale", "master", "lr_scale")}
    return dict(state, opt_state=opt)


# ---------------------------------------------------------------------------
# Trainer loop with periodic checkpoint + straggler watch
# ---------------------------------------------------------------------------

class Trainer:
    def __init__(
        self,
        model_cfg: CHGNetConfig,
        train_cfg: TrainConfig,
        *,
        seed: int = 0,
        mesh: Mesh | None = None,
        ckpt_dir: str | None = None,
        ckpt_every: int = 100,
        keep: int = 3,
        compile_cache: CompileCache | None = None,
        async_ckpt: bool = False,
        shutdown=None,
        donate: bool = True,
        donate_eval: bool = False,
    ):
        self.model_cfg = model_cfg
        self.train_cfg = train_cfg
        # buffer-donation policy, threaded through every step builder's
        # compile-cache ``donate`` flag (donated/undonated builds never
        # collide in the cache): ``donate`` covers train (params/opt_state)
        # and serve (batch); ``donate_eval`` opts the DP eval step into
        # consuming its batch — OFF by default because eval batches are
        # legitimately reused across eval passes
        self.donate = donate
        self.donate_eval = donate_eval
        self.params = chgnet_init(jax.random.PRNGKey(seed), model_cfg)
        # mixed precision (DESIGN.md §4): low-precision param storage gets
        # f32 master weights in the optimizer; low-precision compute gets
        # a loss scaler whose state rides inside opt_state (-> checkpoints
        # and the compile cache carry it with zero signature changes)
        policy = resolve_policy(model_cfg.precision)
        self.opt_state = adam_init(
            self.params,
            master_dtype=jnp.float32 if policy.needs_master_weights
            else None)
        self._scale_kind = train_cfg.loss_scale.resolved_kind(policy)
        if self._scale_kind != "none":
            self.opt_state["loss_scale"] = loss_scale_init(
                train_cfg.loss_scale)
        self.step = 0
        self.mesh = mesh
        self.ckpt_dir = ckpt_dir
        self.ckpt_every = ckpt_every
        self.keep = keep
        # async checkpoints (DESIGN.md §8): snapshot on the loop thread,
        # serialize/fsync/prune on a background writer; sync mode (the
        # default) keeps the reference single-threaded path for tests
        self._ckpt_writer = None
        if async_ckpt and ckpt_dir is not None:
            from repro.runtime.async_ckpt import AsyncCheckpointWriter

            self._ckpt_writer = AsyncCheckpointWriter(ckpt_dir, keep=keep)
        # preemption (DESIGN.md §8): a runtime.fault.GracefulShutdown whose
        # flag is polled every step; on SIGTERM the loop writes a final
        # checkpoint + resume marker and raises PreemptionError
        self.shutdown = shutdown
        # step functions go through the shared repro.batching compile cache
        # so a restarted Trainer (fault tolerance path) reuses traced steps
        cache = compile_cache if compile_cache is not None \
            else global_compile_cache()
        self.compile_cache = cache
        self._build_steps()
        from repro.runtime.fault import DivergenceSentinel, StragglerWatch

        self.straggler = StragglerWatch()
        # divergence rollback (DESIGN.md §8): the sentinel trips on
        # NaN/spike streaks; lr_scale rides in opt_state so the halved LR
        # survives checkpoints; quarantine bookkeeping maps the streak
        # back to dataset indices when batches arrive tagged
        if train_cfg.rollback_on_divergence:
            self.sentinel = DivergenceSentinel(
                window=train_cfg.divergence_window,
                nan_streak=train_cfg.divergence_nan_streak,
                spike_factor=train_cfg.divergence_spike_factor,
                spike_streak=train_cfg.divergence_spike_streak)
            self.opt_state["lr_scale"] = jnp.asarray(1.0, jnp.float32)
        else:
            self.sentinel = None
        self._lr_scale = 1.0
        self.rollbacks = 0
        self.quarantined: set[int] = set()
        self.on_quarantine: Callable[[list[int]], None] | None = None
        from collections import deque

        self._recent_indices: deque = deque(maxlen=max(2 * ckpt_every, 64))
        # live cost-model refit state (TrainConfig.cost_refit_every):
        # (micro_sizes, wall_time) samples, the latest refit CostModel, and
        # the consumer callback (the launcher wires it to
        # BalancedBatchIterator.update_cost_model)
        self._cost_samples: list[tuple[Any, float]] = []
        self._profiled_plans = 0
        self.cost_model = None
        self.on_cost_model: Callable[[Any], None] | None = None

    def _build_steps(self):
        """(Re)build the step functions for the current ``self.mesh``."""
        cache, model_cfg, train_cfg = (self.compile_cache, self.model_cfg,
                                       self.train_cfg)
        if self.mesh is not None:
            # build all three steps: a mesh-mode Trainer must be able to
            # eval and serve too (previously only _train_step existed, so
            # multi-device eval/serve hit undefined attributes).  The
            # donate flags ride the compile-cache keys inside the builders.
            self._train_step = make_dp_train_step(model_cfg, train_cfg,
                                                  self.mesh, cache=cache,
                                                  donate=self.donate)
            self._eval_step = make_dp_eval_step(model_cfg, train_cfg,
                                                self.mesh, cache=cache,
                                                donate=self.donate_eval)
            self._serve_step = make_dp_serve_step(model_cfg, self.mesh,
                                                  cache=cache,
                                                  donate=self.donate)
        else:
            self._train_step, self._eval_step, self._serve_step = (
                make_chgnet_step_fns(model_cfg, train_cfg, cache=cache,
                                     donate=self.donate)
            )
        # accumulation steps are built lazily on the first StepPlan
        self._accum_fns = None

    @property
    def num_devices(self) -> int:
        return int(self.mesh.devices.size) if self.mesh is not None else 1

    def rebuild_mesh(self, mesh: Mesh | None):
        """Re-target the trainer at a (possibly shrunken) mesh.

        The elastic path (``runtime.elastic.elastic_train``) calls this
        after a device drop: params/opt_state are pulled to host first so
        nothing references the dead device's buffers, then the step
        functions are rebuilt (compile-cache keyed by mesh, so returning
        to a previously-seen mesh retraces nothing).
        """
        self.params = jax.device_get(self.params)
        self.opt_state = jax.device_get(self.opt_state)
        self.mesh = mesh
        self._build_steps()

    # -- checkpoint hooks ---------------------------------------------------
    def state(self):
        return {"params": self.params, "opt_state": self.opt_state}

    def save(self, *, wait: bool = False):
        """Checkpoint the current state (async when the Trainer was built
        with ``async_ckpt=True``; ``wait`` forces durability — used for
        final/preemption saves)."""
        if self.ckpt_dir is None:
            return
        meta = {"model_cfg": dataclasses.asdict(self.model_cfg)}
        if self._ckpt_writer is not None:
            self._ckpt_writer.save(self.step, self.state(), extra_meta=meta)
            if wait:
                self._ckpt_writer.flush()
            return
        from repro.runtime.checkpoint import save_checkpoint

        save_checkpoint(
            self.ckpt_dir, self.step, self.state(), keep=self.keep,
            extra_meta=meta,
        )

    def flush_checkpoints(self):
        """Block until every queued async checkpoint is durably written."""
        if self._ckpt_writer is not None:
            self._ckpt_writer.flush()

    def close(self):
        """Flush + stop the async checkpoint writer (idempotent)."""
        if self._ckpt_writer is not None:
            self._ckpt_writer.close()

    def maybe_restore(self) -> bool:
        if self.ckpt_dir is None:
            return False
        # land any in-flight async write first, so "newest valid" below
        # includes it; restore_checkpoint(step=None) then walks newest ->
        # oldest past corrupt/truncated files (DESIGN.md §8)
        self.flush_checkpoints()
        from repro.runtime.checkpoint import latest_step, restore_checkpoint

        if latest_step(self.ckpt_dir) is None:
            return False
        from repro.runtime.checkpoint import MissingLeafError

        # Two independent layout migrations, each applied at most once:
        #   - packed GatedMLP (PR 3): legacy separate core/gate weights are
        #     restored into the legacy-shaped template and packed ONCE here
        #     (checkpoint-load), so no jitted step re-concatenates params;
        #   - precision state (DESIGN.md §4): a legacy f32 checkpoint has
        #     no ``opt_state["loss_scale"]`` / ``opt_state["master"]``
        #     leaves — restore into a stripped template, then re-grow both
        #     from the restored params below.
        # Any other missing leaf (genuinely incompatible checkpoint) —
        # and any failure of a migration attempt — re-raises the FIRST
        # error so the real mismatch surfaces, not a misleading one.
        packed_keys = ("['w']", "['b']", "['ln_scale']", "['ln_bias']")
        precision_keys = ("['loss_scale']", "['master']", "['lr_scale']")
        from repro.core.interaction import (
            gated_mlp_legacy_template, pack_gated_mlp_params)

        wants_master = "master" in self.opt_state
        template = self.state()
        stripped = packed = False
        first_err = None
        while True:
            try:
                state, step, _ = restore_checkpoint(self.ckpt_dir, template)
                break
            except MissingLeafError as missing:
                first_err = first_err or missing
                if not stripped and any(k in missing.leaf_path
                                        for k in precision_keys):
                    template = _strip_precision_state(template)
                    stripped = True
                    continue
                if not packed and missing.leaf_path.endswith(packed_keys):
                    template = gated_mlp_legacy_template(template)
                    packed = True
                    continue
                # no migration applies: THIS leaf is genuinely missing
                # from the checkpoint (migrations only strip/rename their
                # own leaves), so it is the real mismatch to surface
                raise missing
            except (KeyError, ValueError):
                if first_err is not None:
                    raise first_err
                raise
        if packed:
            state = pack_gated_mlp_params(state)
        self.params, self.opt_state = state["params"], state["opt_state"]
        if stripped:
            # legacy-f32 -> mixed-precision migration: master weights are
            # re-grown from the restored params (exact for policies that
            # store f32 params) and the scaler restarts at init_scale
            if wants_master:
                self.opt_state["master"] = cast_float_tree(
                    self.params, jnp.float32)
            if self._scale_kind != "none":
                self.opt_state["loss_scale"] = loss_scale_init(
                    self.train_cfg.loss_scale)
            if self.train_cfg.rollback_on_divergence:
                # legacy checkpoint without lr_scale: re-grow it at the
                # trainer's CURRENT cumulative rollback factor, so a
                # post-rollback restore keeps the backed-off LR
                self.opt_state["lr_scale"] = jnp.asarray(
                    self._lr_scale, jnp.float32)
        self.step = step
        return True

    # -- eval / serve -------------------------------------------------------
    def evaluate(self, batch) -> dict:
        """Loss metrics on one batch (stacked per-device leaves in mesh mode)."""
        return {k: float(v)
                for k, v in self._eval_step(self.params, batch).items()}

    def serve(self, batch):
        """One inference step (E/F/sigma/magmom); Table II's workload."""
        return self._serve_step(self.params, batch)

    # -- gradient accumulation (DESIGN.md §6) --------------------------------
    def _get_accum_fns(self):
        if self._accum_fns is None:
            self._accum_fns = make_chgnet_accum_step_fns(
                self.model_cfg, self.train_cfg, mesh=self.mesh,
                cache=self.compile_cache, donate=self.donate)
        return self._accum_fns

    def _step_plan(self, plan: StepPlan):
        """One optimizer step over a balanced multi-bucket StepPlan:
        per-microbatch grads (global-denominator partial losses) are
        summed on device, then applied once — numerically the same update
        a single big-batch step would take (tests: test_balance)."""
        grad_step, apply_step = self._get_accum_fns()
        scaler = self.opt_state.get("loss_scale")
        scale = scaler["scale"] if scaler is not None \
            else jnp.asarray(1.0, jnp.float32)
        denoms = {k: jnp.asarray(v) for k, v in plan.denoms.items()}
        # per-microbatch timing for the live cost-model refit: only when
        # enabled (the block_until_ready sync breaks async dispatch, so
        # the default path stays fully pipelined), only past the compile
        # warmup, and only for plans that carry their real feature sizes
        profile = (self.train_cfg.cost_refit_every > 0
                   and plan.micro_sizes is not None)
        gsum = ssum = None
        for i, micro in enumerate(plan.micro):
            t0 = time.perf_counter() if profile else 0.0
            grads, sums = grad_step(self.params, micro, denoms, scale)
            if profile:
                jax.block_until_ready(grads)
                if self._profiled_plans >= self.train_cfg.cost_refit_warmup:
                    self._cost_samples.append(
                        (plan.micro_sizes[i], time.perf_counter() - t0))
            if gsum is None:
                gsum, ssum = grads, sums
            else:
                gsum = jax.tree.map(jnp.add, gsum, grads)
                ssum = jax.tree.map(jnp.add, ssum, sums)
        if profile:
            self._profiled_plans += 1
            del self._cost_samples[:-self.train_cfg.cost_refit_window]
        return apply_step(self.params, self.opt_state, gsum, ssum, denoms,
                          jnp.asarray(self.step))

    def _maybe_refit_cost_model(self):
        """Refit the LPT cost model from recorded (sizes, time) samples
        every ``cost_refit_every`` optimizer steps and push it to
        ``on_cost_model`` (DESIGN.md §6).  Needs >= 4 samples (the affine
        fit has 4 coefficients); nonneg-clamped lstsq, host-side only."""
        every = self.train_cfg.cost_refit_every
        if every <= 0 or self.step % every or len(self._cost_samples) < 4:
            return
        import numpy as np

        from repro.batching.cost import fit_cost_model

        sizes = np.asarray([s for s, _ in self._cost_samples], np.float64)
        times = np.asarray([t for _, t in self._cost_samples], np.float64)
        self.cost_model = fit_cost_model(sizes, times)
        if self.on_cost_model is not None:
            self.on_cost_model(self.cost_model)

    # -- divergence rollback / preemption (DESIGN.md §8) ---------------------
    def _rollback(self):
        """Sentinel tripped: quarantine the streak's batches, restore the
        newest valid checkpoint, and (optionally) back the LR off."""
        self.rollbacks += 1
        if self.rollbacks > self.train_cfg.max_rollbacks:
            raise FloatingPointError(
                f"divergence persists after {self.train_cfg.max_rollbacks} "
                f"rollbacks (step {self.step})")
        # the streak's batches are the prime suspects: quarantine their
        # dataset indices so the iterator skips them after the restore
        trip_len = self.sentinel.last_trip_len if self.sentinel else 0
        fresh: set[int] = set()
        for _, idx in list(self._recent_indices)[-max(trip_len, 1):]:
            fresh.update(int(i) for i in idx)
        fresh -= self.quarantined
        if fresh:
            self.quarantined |= fresh
            if self.on_quarantine is not None:
                self.on_quarantine(sorted(fresh))
        if not self.maybe_restore():
            raise FloatingPointError(
                f"divergence at step {self.step} with no checkpoint to "
                "roll back to (ckpt_dir unset or empty)")
        factor = self.train_cfg.rollback_lr_factor
        if factor < 1.0:
            self._lr_scale *= factor
            self.opt_state["lr_scale"] = jnp.asarray(
                self._lr_scale, jnp.float32)

    def _preempt(self):
        """SIGTERM (or any GracefulShutdown signal): durably checkpoint,
        drop a resume marker, and raise PreemptionError — which
        ``run_with_restarts`` never retries (handing control to the
        scheduler is the point)."""
        from repro.runtime.fault import PreemptionError, write_resume_marker

        if self.ckpt_dir is not None:
            self.save(wait=True)
            signum = self.shutdown.signum if self.shutdown else None
            write_resume_marker(self.ckpt_dir, self.step,
                                reason=f"signal {signum}")
        raise PreemptionError(self.step)

    # -- loop -----------------------------------------------------------------
    def train(self, batches, max_steps: int | None = None,
              fault_injector=None) -> list[dict]:
        history = []
        try:
            return self._train_loop(batches, history, max_steps,
                                    fault_injector)
        except Exception as exc:
            # steps completed before the failure are real progress — let
            # recovery paths (runtime.elastic.elastic_train) keep their
            # metrics instead of losing them with the raise
            exc.partial_history = history
            raise

    def _train_loop(self, batches, history, max_steps, fault_injector):
        for batch in batches:
            if max_steps is not None and self.step >= max_steps:
                break
            if self.shutdown is not None and self.shutdown.requested:
                self._preempt()
            with spans.step("train.step", self.step):
                self._train_one(batch, history, fault_injector)
        return history

    def _train_one(self, batch, history, fault_injector) -> None:
        """One optimizer step, inside the ``repro.train.step`` marker:
        ``repro.train.dispatch`` (the step's call), ``repro.train.loss_read``
        (the blocking read of its loss) and ``repro.train.ckpt`` (a
        save).  A rolled-back or restored step appends no history."""
        import numpy as np

        from repro.data.pipeline import TaggedBatch

        t0 = time.perf_counter()
        if fault_injector is not None:
            fault_injector.maybe_fail(self.step)
        indices = None
        if isinstance(batch, TaggedBatch):
            indices, batch = batch.indices, batch.batch
        with spans.span("train.dispatch"):
            if isinstance(batch, StepPlan):
                self.params, self.opt_state, metrics = self._step_plan(batch)
            else:
                self.params, self.opt_state, metrics = self._train_step(
                    self.params, self.opt_state, batch,
                    jnp.asarray(self.step)
                )
        if indices is not None:
            self._recent_indices.append(
                (self.step, np.asarray(indices)))
        with spans.span("train.loss_read"):
            loss = float(metrics["loss"])
        # a scaler-skipped overflow step (grads_finite == 0) is NOT
        # poison: the update was rejected and the scale backed off,
        # so params are untouched (DESIGN.md §4)
        skipped = not bool(metrics.get("grads_finite", 1.0))
        if self.sentinel is not None:
            if self.sentinel.record(loss, scaler_skipped=skipped):
                self._rollback()
                return
        elif not jnp.isfinite(loss) and not skipped:
            # legacy NaN guard: roll back rather than poison the run
            if self.maybe_restore():
                return
            raise FloatingPointError(f"non-finite loss at step {self.step}")
        self.step += 1
        self.straggler.record(time.perf_counter() - t0)
        self._maybe_refit_cost_model()
        history.append({k: float(v) for k, v in metrics.items()})
        if self.ckpt_dir is not None and self.step % self.ckpt_every == 0:
            # only checkpoint states the sentinel considers healthy,
            # so every file on disk is a known-good rollback target
            if self.sentinel is None or not self.sentinel.suspicious:
                with spans.span("train.ckpt"):
                    self.save()

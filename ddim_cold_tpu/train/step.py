"""The SPMD training step — replaces DDP + AMP + GradScaler + per-step
scheduler (SURVEY.md C16).

One jitted ``train_step(state, batch, rng) → (state, loss)`` carries the whole
reference inner loop (multi_gpu_trainer.py:109-134): forward in the model's
compute dtype (bf16 under "AMP" — no GradScaler; bf16 keeps fp32 range so loss
scaling is unnecessary on TPU), smooth-L1 loss in f32, global-norm clip 1.0,
AdamW(wd=0.05) with a per-step cosine schedule to 0 — the optax chain mirrors
torch's clip→AdamW→CosineAnnealingLR order of operations.

Parallelism is carried by the *data*, not the code: params live replicated (or
tensor-sharded) on the mesh, the batch is sharded on 'data', and XLA inserts
the gradient psum over ICI where DDP used an NCCL allreduce. The same step
function serves 1 chip or a full slice.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import optax
from flax.training import train_state

from ddim_cold_tpu.obs import scopes
from ddim_cold_tpu.ops.losses import smooth_l1
from ddim_cold_tpu.utils import profiling  # also: its compile listener, before the first compile


class EmaTrainState(train_state.TrainState):
    """TrainState plus an optional EMA (exponential moving average) shadow of
    the params — the standard diffusion-training practice of sampling from
    smoothed weights (the reference has no EMA weights; this is a
    beyond-parity, opt-in feature: ``ema_decay: 0`` keeps it off and the
    field ``None``, so default runs are byte-identical to before)."""

    ema_params: Any = None


def make_optimizer(lr: float, total_steps: int) -> optax.GradientTransformation:
    """clip_by_global_norm(1.0) → AdamW(cosine→0, wd=0.05)
    (multi_gpu_trainer.py:89-92,130)."""
    schedule = optax.cosine_decay_schedule(init_value=lr, decay_steps=total_steps, alpha=0.0)
    return optax.chain(
        optax.clip_by_global_norm(1.0),
        optax.adamw(schedule, b1=0.9, b2=0.999, eps=1e-8, weight_decay=0.05),
    )


def create_train_state(model, rng: jax.Array, lr: float, total_steps: int,
                       sample_batch, ema_decay: float = 0.0) -> EmaTrainState:
    """Initialize params (same rng on every host ⇒ identical init, making the
    reference's save-to-file-and-sleep broadcast (multi_gpu_trainer.py:71-80)
    unnecessary) and wrap them with the optimizer. ``ema_decay`` > 0 also
    seeds an EMA shadow of the params (see :class:`EmaTrainState`)."""
    noisy, _, t = sample_batch
    # one program: eagerly every initializer compiles its handful of ops once
    # a parameter shape (19 s of a cold start at toy size, bitwise the same)
    params = jax.jit(model.init)(rng, jnp.asarray(noisy),
                                 jnp.asarray(t))["params"]
    state = EmaTrainState.create(
        apply_fn=model.apply, params=params, tx=make_optimizer(lr, total_steps),
        ema_params=jax.tree.map(jnp.copy, params) if ema_decay else None,
    )
    # flax seeds step=0 as a python int → weak-typed int32 through the jitted
    # step, while a checkpoint-restored step is strong-typed — two avals, two
    # compiles across a resume. Anchor it once here (GRAFT-J002).
    return state.replace(step=jnp.asarray(0, jnp.int32))


def make_train_step(model, apply_fn: Optional[Callable] = None,
                    prepare: Optional[Callable] = None,
                    ema_decay: float = 0.0,
                    grad_accum: int = 1,
                    moe_aux_weight: float = 0.0,
                    steps_per_dispatch: int = 1) -> Callable:
    """``(state, batch, rng, loss_rec) → (state, loss, loss_rec)``.

    The EMA train loss (0.99/0.01, multi_gpu_trainer.py:126) is carried as a
    device scalar so the host only syncs at log points — the reference's
    per-step ``loss.item()`` would serialize the TPU pipeline. State buffers
    are donated (in-place update, no double-buffered params in HBM).

    ``apply_fn`` overrides ``model.apply`` with the same signature — the hook
    pipeline parallelism uses (parallel.pipeline.make_pipelined_apply).

    ``prepare`` is the device-side corruption hook: ``(raw_batch, rng) →
    (noisy, target, t)`` traced into the step (ops/degrade.make_cold_prepare),
    letting the host ship clean bases instead of degraded pairs.

    ``ema_decay`` > 0 updates the state's EMA param shadow each step
    (``ema ← d·ema + (1−d)·p``, plain decay, no bias correction — the warmup
    bias is irrelevant over a full training run and the seed is the init
    params, not zeros). Elementwise, so it fuses into the optimizer tail and
    inherits whatever sharding the params carry.

    ``grad_accum`` > 1 splits each step's batch into that many equal
    micro-slices and runs them through one ``lax.scan``, averaging the
    per-slice gradients before the single optimizer update — the standard
    big-batch-on-small-HBM tool (absent upstream). Peak activation memory
    drops ~grad_accum×; with dropout off the result is numerically
    equivalent to the unaccumulated step (smooth-L1 is a mean, and the mean
    of equal-sized slice means is the full-batch mean — only the float
    summation order differs, ~1e-7); with dropout on each slice folds its
    own mask key, which is the correct regularization, not a divergence.
    Slices are INTERLEAVED (slice j = rows j, j+ga, …): under a
    batch-dim-sharded mesh each slice stays evenly distributed over the
    'data' axis, where a contiguous split would park whole slices on one
    device and idle the rest.

    ``moe_aux_weight`` > 0 (Switch-MoE models only, models/moe.py): the
    forward runs with the ``losses`` collection mutable and the Switch
    load-balance loss — the mean of the per-block ``sow``n values — is
    added to the smooth-L1 with this coefficient.

    ``steps_per_dispatch`` > 1 changes the batch contract: every leaf gains
    a leading axis of that length (n stacked per-step batches) and ONE
    dispatch runs n full optimizer steps through a ``lax.scan``, returning
    the mean loss over them. Each inner step is the identical single-step
    math (the per-step rng/prepare folds key off ``state.step``, which
    advances inside the scan), so the result matches n sequential calls that
    pass the same ``rng``. This is the host-link lever: n× fewer
    host↔device round trips and n× larger transfers, for regimes where
    per-dispatch host latency dominates the step time (its effect on the
    current machine is not measured).
    """
    moe_on = moe_aux_weight > 0 and getattr(model, "num_experts", 1) > 1
    if (moe_on and apply_fn is not None
            and not getattr(apply_fn, "supports_losses", False)):
        raise ValueError(
            "moe_aux_weight requires an apply path that threads the "
            "'losses' collection — model.apply, or a custom apply_fn that "
            "sets .supports_losses (e.g. make_pipelined_apply)")
    apply_fn = apply_fn or model.apply
    if grad_accum < 1:
        raise ValueError(f"grad_accum must be >= 1, got {grad_accum}")
    if not 0.0 <= ema_decay < 1.0:  # same bound config.py enforces — direct
        raise ValueError(  # API callers must not bypass it (1.0 freezes the
            f"ema_decay must be in [0, 1), got {ema_decay!r}")  # shadow)
    if steps_per_dispatch < 1:
        raise ValueError(
            f"steps_per_dispatch must be >= 1, got {steps_per_dispatch}")

    def step_body(state: EmaTrainState, batch, rng: jax.Array,
                  loss_rec: jax.Array):
        if prepare is not None:
            # distinct fold constant: fold_in(rng, step+1) would be bit-equal
            # to the NEXT step's dropout key, correlating a stochastic
            # prepare's noise with the following step's dropout mask
            batch = prepare(
                batch, jax.random.fold_in(jax.random.fold_in(rng, 0x5EED), state.step))
        noisy, target, t = batch
        dropout_rng = jax.random.fold_in(rng, state.step)

        def loss_fn(params, noisy, target, t, drop_rng):
            if moe_on:
                pred, aux_vars = apply_fn(
                    {"params": params}, noisy, t, deterministic=False,
                    rngs={"dropout": drop_rng}, mutable=["losses"],
                )
                sown = jax.tree.leaves(aux_vars.get("losses", {}))
                # mean over LAYERS, layout-independent: the unrolled model
                # sows depth scalar leaves, the scan_blocks layout ONE
                # (depth,)-stacked leaf — normalize by total element count,
                # not leaf count, so both layouts weight the aux identically
                n_vals = sum(s.size for s in sown)
                aux = (sum(jnp.sum(s) for s in sown) / n_vals
                       if sown else 0.0)
                return smooth_l1(pred, target) + moe_aux_weight * aux
            pred = apply_fn(
                {"params": params}, noisy, t, deterministic=False,
                rngs={"dropout": drop_rng},
            )
            return smooth_l1(pred, target)

        if grad_accum == 1:
            loss, grads = jax.value_and_grad(loss_fn)(
                state.params, noisy, target, t, dropout_rng)
        else:
            b = noisy.shape[0]
            if b % grad_accum:
                raise ValueError(
                    f"batch {b} not divisible by grad_accum {grad_accum}")
            split = lambda x: x.reshape(  # noqa: E731 — interleaved: see doc
                (b // grad_accum, grad_accum) + x.shape[1:]).swapaxes(0, 1)

            def slice_grad(carry, sl):
                mb_noisy, mb_target, mb_t, i = sl
                loss_i, g_i = jax.value_and_grad(loss_fn)(
                    state.params, mb_noisy, mb_target, mb_t,
                    jax.random.fold_in(dropout_rng, i))
                return (jax.tree.map(jnp.add, carry[0], g_i),
                        carry[1] + loss_i), None

            zero = (jax.tree.map(jnp.zeros_like, state.params),
                    jnp.float32(0.0))
            (gsum, lsum), _ = jax.lax.scan(
                slice_grad, zero,
                (split(noisy), split(target), split(t),
                 jnp.arange(grad_accum)))
            grads = jax.tree.map(lambda g: g / grad_accum, gsum)
            loss = lsum / grad_accum
        # clip, AdamW, the parameters' update and the EMA shadow's: the
        # ``optimizer`` layer of obs.scopes
        with profiling.scope("train/optimizer"):
            new_state = state.apply_gradients(grads=grads)
            if ema_decay:
                if state.ema_params is None:  # trace-time: silently training
                    raise ValueError(  # with no shadow would surface only
                        # when bestloss_ema is missing at the end of the run
                        "ema_decay > 0 but the state carries no ema_params — "
                        "create it with create_train_state(..., "
                        "ema_decay=...) or seed "
                        "state.replace(ema_params=...)")
                new_state = new_state.replace(
                    ema_params=optax.incremental_update(
                        new_state.params, state.ema_params,
                        step_size=1.0 - ema_decay))
        return new_state, loss, loss_rec * 0.99 + loss * 0.01

    if steps_per_dispatch == 1:
        return scopes.noted("train/step", partial(
            jax.jit, donate_argnums=(0, 3))(step_body))

    @partial(jax.jit, donate_argnums=(0, 3))
    def multi_step(state: EmaTrainState, stacked_batch, rng: jax.Array,
                   loss_rec: jax.Array):
        def scan_body(carry, bt):
            st, rec = carry
            st, loss, rec = step_body(st, bt, rng, rec)
            return (st, rec), loss

        (state, loss_rec), losses = jax.lax.scan(
            scan_body, (state, loss_rec), stacked_batch,
            length=steps_per_dispatch)
        return state, losses.mean(), loss_rec

    return scopes.noted("train/multi_step", multi_step)


def make_eval_step(model, apply_fn: Optional[Callable] = None,
                   prepare: Optional[Callable] = None) -> Callable:
    apply_fn = apply_fn or model.apply

    @jax.jit
    def eval_step(params, batch):
        if prepare is not None:
            batch = prepare(batch, jax.random.PRNGKey(0))
        noisy, target, t = batch
        pred = apply_fn({"params": params}, noisy, t, deterministic=True)
        return smooth_l1(pred, target)

    return eval_step

"""The compiled train/eval steps — the framework's hot loop.

The reference's hot loop (`/root/reference/cifar_example_ddp.py:94-107`,
SURVEY.md §3.2) is five eager calls per step with NCCL allreduces fired from
C++ autograd hooks during `loss.backward()`. Here the *entire* loop body is
one jitted XLA program:

    loss, grads = value_and_grad(xent ∘ model)(params, global_batch)
    params, opt = sgd(params, grads, lr(step))

with the global batch *sharded* over the ``data`` mesh axis and the state
*replicated*. Because the loss is a mean over the logical global batch, XLA's
partitioner (GSPMD) materializes the cross-chip gradient all-reduce inside
the compiled program — the same collective DDP runs from hooks, but fused,
scheduled alongside compute by the compiler, and overlap-optimized over ICI.
Donation reuses the state's device buffers across steps (no allocator
churn). Single-chip is the same program on a mesh of one.
"""

from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from tpu_dp.models.outputs import RowLoss
from tpu_dp.parallel.sharding import batch_sharding, replicated_sharding
from tpu_dp.train.optim import Optimizer
from tpu_dp.train.schedule import Schedule
from tpu_dp.train.state import TrainState


def cross_entropy_loss(
    logits: jnp.ndarray,
    labels: jnp.ndarray,
    weight: jnp.ndarray | None = None,
) -> jnp.ndarray:
    """(Weighted) mean softmax cross-entropy from integer labels.

    Parity with `nn.CrossEntropyLoss()` (reduction='mean', raw logits in)
    (`/root/reference/cifar_example.py:63`). Computed in float32 regardless
    of the model's compute dtype (bf16-safe reduction). ``weight`` masks
    padded examples out of the mean (eval's final partial batch).
    """
    logits = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    true_logit = jnp.take_along_axis(
        logits, labels[:, None].astype(jnp.int32), axis=-1
    )[:, 0]
    per_example = logz - true_logit
    if weight is None:
        return jnp.mean(per_example)
    return jnp.sum(per_example * weight) / jnp.maximum(jnp.sum(weight), 1.0)


def _to_varying(x: jnp.ndarray, axis_name: str) -> jnp.ndarray:
    """Cast a replication-invariant value to device-varying under shard_map."""
    return jax.lax.pcast(x, axis_name, to="varying")


def _shard_map(f, mesh, in_specs, out_specs):
    """`jax.shard_map` with replication checking on (its default)."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs, out_specs=out_specs)


def _maybe_normalize(images: jnp.ndarray) -> jnp.ndarray:
    """Fused on-device normalize for uint8 batches (pipeline default).

    Same transform as `tpu_dp.data.cifar.normalize` (reference parity:
    ToTensor + Normalize(0.5, 0.5), `cifar_example.py:38-40`); XLA fuses the
    convert+scale into the consumer of the batch.
    """
    if images.dtype == jnp.uint8:
        from tpu_dp.data.cifar import normalize

        return normalize(images)  # works on traced arrays; one source of truth
    return images


def _inputs_and_labels(batch):
    """``(inputs, labels)`` of a batch as its data set ships it: images
    (normalized here) with their class labels, or rows of tokens, which are
    their own targets (`tpu_dp.data.tokens`)."""
    if "tokens" in batch:
        return batch["tokens"], batch["tokens"]
    # an inference batch carries no labels
    return _maybe_normalize(batch["image"]), batch.get("label")


def _augment_phase(augment_fn) -> str:
    """The phase name of what runs in the step's augmentation seam: a
    function may carry its own (the diffusion noise is ``tpu_dp.noise``)."""
    return getattr(augment_fn, "phase", "tpu_dp.augment")


def _loss_of(loss_impl, outputs, labels):
    """The step's loss from a model's outputs: logits go through
    ``loss_impl``; a model that computes its loss by the row
    (`tpu_dp.models.outputs.RowLoss`) hands the rows over."""
    if isinstance(outputs, RowLoss):
        return jnp.mean(outputs.loss)
    return loss_impl(outputs, labels)


def _correct_and_counters(outputs, labels):
    """``(correct, counters)``: an argmax over logits and no counters, or
    what a `RowLoss` says was judged right, with the model's counters."""
    if isinstance(outputs, RowLoss):
        return jnp.sum(outputs.correct), outputs.counters
    return jnp.sum(jnp.argmax(outputs, axis=-1) == labels), None


def _apply_model(model, state: TrainState, images, train: bool):
    """Run the model, handling BatchNorm's mutable running stats."""
    if state.has_batch_stats:
        variables = {"params": state.params, "batch_stats": state.batch_stats}
        if train:
            logits, mutated = model.apply(
                variables, images, train=True, mutable=["batch_stats"]
            )
            return logits, mutated["batch_stats"]
        return model.apply(variables, images, train=False), state.batch_stats
    return model.apply({"params": state.params}, images, train=train), {}


def _forward_backward(model, loss_impl, state: TrainState, images, labels,
                      cast_params=None):
    """Shared fwd+bwd block: loss, grads, updated BN stats, correct count,
    and the model's counters (None for a model that publishes none).

    Train batches are always full (drop_remainder enforced), so no weight
    mask on the training loss. One block for the GSPMD and the
    explicit-`shard_map` programs, so they cannot drift apart.

    ``cast_params`` (per-leaf, applied *before* differentiation) is the
    explicit-collectives path's varying-cast hook: under shard_map's
    replication typing, differentiating a *varying* loss wrt *invariant*
    params would insert an implicit cross-shard psum (the cotangent of the
    invariant→varying broadcast) before the explicit collective — casting
    outside the diff'd function keeps AD local, per-shard grads out.
    """
    params0 = state.params
    if cast_params is not None:
        params0 = jax.tree_util.tree_map(cast_params, params0)

    def loss_fn(params):
        outputs, new_batch_stats = _apply_model(
            model, state.replace(params=params), images, train=True
        )
        return _loss_of(loss_impl, outputs, labels), (outputs,
                                                      new_batch_stats)

    (loss, (outputs, new_batch_stats)), grads = jax.value_and_grad(
        loss_fn, has_aux=True
    )(params0)
    correct, counters = _correct_and_counters(outputs, labels)
    return loss, grads, new_batch_stats, correct, counters


def _apply_update(
    optimizer: Optimizer, schedule: Schedule, state: TrainState, grads,
    new_batch_stats, lr_scale=None, new_residuals=None,
):
    """Shared optimizer tail: LR lookup, update, next TrainState.

    ``lr_scale`` is the guardrail layer's LR ease-in knob (a replicated
    runtime scalar from ``guard_in``): after a rollback the policy ramps it
    from ``guard.lr_ease_start`` back to 1.0 so the replayed window does not
    re-trace the exact trajectory that diverged. None (the default, every
    non-sentinel program) leaves the schedule untouched — and the trace
    unchanged.

    ``new_residuals`` carries the int8 wire codec's updated error-feedback
    state out of the reduce hook (None — every non-quantized program —
    passes the state's residuals through untouched: {} for them, so the
    compiled HLO is unchanged).
    """
    lr = schedule(state.step)
    if lr_scale is not None:
        lr = lr * lr_scale
    new_params, new_opt_state = optimizer.update(
        grads, state.opt_state, state.params, lr
    )
    new_state = TrainState(
        step=state.step + 1,
        params=new_params,
        opt_state=new_opt_state,
        batch_stats=new_batch_stats,
        residuals=(state.residuals if new_residuals is None
                   else new_residuals),
    )
    return new_state, lr


def _select_loss_impl(use_pallas_xent: bool):
    """One source of truth for the loss implementation switch."""
    if use_pallas_xent:
        from tpu_dp.ops.xent import mean_softmax_xent

        return mean_softmax_xent
    return cross_entropy_loss


def default_guard_in():
    """The neutral ``guard_in`` pytree the sentinel-enabled steps take.

    A replicated input of four scalars (host-built numpy so constructing it
    never touches a device):

    - ``loss_cap`` — device-side skip threshold: a finite training loss
      above it is treated like a non-finite one (update not applied). The
      guard policy arms it from the trailing window's median/MAD under
      ``guard.action=skip``; +inf disarms.
    - ``lr_scale`` — multiplies the scheduled LR (rollback ease-in; 1.0 is
      exact identity, bitwise).
    - ``fault_step`` / ``fault_scale`` — the deterministic fault-injection
      seam (``TPU_DP_FAULT`` ``nan:``/``spike:`` specs, docs/RESILIENCE.md):
      at ``state.step == fault_step`` the loss and gradients are multiplied
      by ``fault_scale`` *inside the compiled program* (NaN for ``nan:``,
      a large finite scale for ``spike:``). ``fault_step=-1`` never fires,
      and the disarmed multiply-by-1.0 is bitwise identity.

    Feeding the same dtypes every call keeps the trace signature stable
    (one cache entry; the RecompileGuard stays silent).
    """
    import numpy as np

    return {
        "loss_cap": np.float32(np.inf),
        "lr_scale": np.float32(1.0),
        "fault_step": np.int32(-1),
        "fault_scale": np.float32(1.0),
    }


def guard_in_struct():
    """ShapeDtypeStruct twin of `default_guard_in` (AOT fingerprinting)."""
    return {k: jax.ShapeDtypeStruct(v.shape, v.dtype)
            for k, v in default_guard_in().items()}


def _inject_guard_fault(step, loss, grads, guard_in):
    """The ``nan:``/``spike:`` injection seam, compiled into the step.

    Sits on the *pre-reduction* gradients so a rank-gated fault propagates
    to every replica through the gradient collective exactly like a real
    corrupted batch would (explicit-collectives paths; under GSPMD the
    partitioner may place the multiply after the inferred all-reduce, so
    rank-gated injection there stays rank-local — documented in
    docs/RESILIENCE.md). Disarmed (``fault_step=-1``) this is a
    multiply-by-1.0: bitwise identity.
    """
    fire = step == guard_in["fault_step"]
    factor = jnp.where(fire, guard_in["fault_scale"], jnp.float32(1.0))
    loss = loss * factor.astype(loss.dtype)
    grads = jax.tree_util.tree_map(
        lambda g: g * factor.astype(g.dtype), grads
    )
    return loss, grads


def _grad_health(grads, loss, health_reduce=None):
    """The on-device health summary: global grad-norm + finite-ness flag.

    ``sum(g²)`` in f32 over every leaf; a single NaN/Inf anywhere in the
    gradient tree makes the sum non-finite, so one scalar carries both the
    norm and the finite-ness signal. ``health_reduce`` closes the
    cross-replica gap on the sharded-update path (each replica holds a
    1/world gradient shard, so the local sum-of-squares is partial — one
    extra *scalar* psum over the data axis, the only collective the
    sentinel ever adds; replicated/GSPMD paths compute on already-reduced
    gradients and add none).
    """
    sumsq = jnp.zeros((), jnp.float32)
    for g in jax.tree_util.tree_leaves(grads):
        sumsq = sumsq + jnp.sum(jnp.square(g.astype(jnp.float32)))
    if health_reduce is not None:
        sumsq = health_reduce(sumsq)
    finite = jnp.isfinite(loss.astype(jnp.float32)) & jnp.isfinite(sumsq)
    return jnp.sqrt(sumsq), finite


def _sentinel_tail(optimizer, schedule, state, grads, new_batch_stats,
                   loss, correct, count, guard_in, health_reduce,
                   opt_pred_cast=None, new_residuals=None,
                   extra_metrics=None):
    """The sentinel step tail: health summary → guarded update → metrics.

    The update is computed unconditionally and then *selected against*: a
    step whose loss/gradients are non-finite, or whose loss exceeds the
    armed ``loss_cap``, emits the ORIGINAL state — params, optimizer
    state, BatchNorm statistics and the step counter all unchanged, as if
    the batch was never seen (the quarantine contract: the final params of
    a run that skipped batch K are bitwise those of a run that never saw
    it). The decision is computed from globally-reduced values, so every
    replica selects identically — no divergence, no extra sync.

    Metrics grow the health fields the guard policy consumes (one host
    fetch per window, at the existing fence boundary): ``loss_raw`` /
    ``grad_norm`` (unmasked), ``applied`` (0 = quarantined). ``loss`` and
    ``correct`` are masked to zero on skipped steps so the epoch
    accumulators never ingest a NaN.
    """
    if guard_in is None:
        guard_in = default_guard_in()
    with jax.named_scope("tpu_dp.sentinel"):
        gnorm, finite = _grad_health(grads, loss, health_reduce)
        applied = finite & (loss.astype(jnp.float32) <= guard_in["loss_cap"])
    with jax.named_scope("tpu_dp.update"):
        new_state, lr = _apply_update(
            optimizer, schedule, state, grads, new_batch_stats,
            lr_scale=guard_in["lr_scale"], new_residuals=new_residuals,
        )
        # ``opt_pred_cast`` (sharded update only): the opt-state leaves
        # are device-varying 1/world shards under shard_map's replication
        # typing, so the invariant skip predicate is cast varying for that
        # subtree (`_to_varying`; everywhere else the whole state is
        # replicated and no cast is passed). The int8 codec's residuals
        # share the varying predicate: a quarantined batch's quantization
        # error must be forgotten WITH the batch, or the next step's error
        # feedback would re-inject a slice of the poisoned gradient.
        opt_pred = applied if opt_pred_cast is None else opt_pred_cast(applied)
        new_state = TrainState(
            step=jnp.where(applied, new_state.step, state.step),
            params=jax.tree_util.tree_map(
                lambda n, o: jnp.where(applied, n, o),
                new_state.params, state.params),
            opt_state=jax.tree_util.tree_map(
                lambda n, o: jnp.where(opt_pred, n, o),
                new_state.opt_state, state.opt_state),
            batch_stats=jax.tree_util.tree_map(
                lambda n, o: jnp.where(applied, n, o),
                new_state.batch_stats, state.batch_stats),
            residuals=jax.tree_util.tree_map(
                lambda n, o: jnp.where(opt_pred, n, o),
                new_state.residuals, state.residuals),
        )
    metrics = {
        "loss": jnp.where(applied, loss, jnp.zeros_like(loss)),
        "correct": jnp.where(applied, correct, jnp.zeros_like(correct)),
        "count": count,
        "lr": lr,
        "loss_raw": loss,
        "grad_norm": gnorm,
        "applied": applied.astype(jnp.int32),
    }
    if extra_metrics:
        metrics.update(extra_metrics)
    return new_state, metrics


def _make_update_tail(optimizer, schedule, reduce_fn, sentinel,
                      health_reduce, opt_pred_cast):
    """What both step bodies do with one update's gradients: the
    cross-replica reduce hook, then the (guarded) optimizer update and the
    step's metrics — stated once, so the plain and the accumulating body
    cannot drift apart."""

    def finish(state, grads, new_batch_stats, loss, correct, count,
               counters, guard_in):
        new_residuals, extra = None, {}
        if reduce_fn is not None:
            with jax.named_scope("tpu_dp.grad_reduce"):
                (grads, loss, correct, count, new_batch_stats,
                 new_residuals, extra) = reduce_fn(
                    grads, loss, correct, count, new_batch_stats,
                    state.residuals, counters=counters,
                )
        elif counters is not None:
            extra = {"counters": counters}
        if sentinel:
            return _sentinel_tail(
                optimizer, schedule, state, grads, new_batch_stats,
                loss, correct, count, guard_in, health_reduce,
                opt_pred_cast=opt_pred_cast, new_residuals=new_residuals,
                extra_metrics=extra,
            )
        with jax.named_scope("tpu_dp.update"):
            new_state, lr = _apply_update(
                optimizer, schedule, state, grads, new_batch_stats,
                new_residuals=new_residuals,
            )
        metrics = {"loss": loss, "correct": correct, "count": count,
                   "lr": lr}
        metrics.update(extra)
        return new_state, metrics

    return finish


def _make_step_body(model, optimizer, schedule, loss_impl, augment_fn,
                    reduce_fn=None, cast_params=None, sentinel=False,
                    health_reduce=None, opt_pred_cast=None):
    """The single-microbatch step body of every `make_train_step` program
    (accum_steps=1), called once or scanned — one source of truth for
    normalize → augment → fwd/bwd → [cross-replica reduce] → update →
    metrics, so the host-loop and device-loop paths cannot drift apart.

    ``reduce_fn(grads, loss, correct, count, batch_stats, residuals)`` is
    the explicit-collectives hook: the GSPMD path passes None (the
    partitioner infers the gradient all-reduce from shardings), the
    `shard_map` path injects the typed collective wrappers between the
    per-shard grads and the optimizer update — the one placement
    `tpu_dp.analysis` verifies. It returns the reduced values plus the
    (possibly updated) error-feedback residuals and an extra-metrics dict
    ({} everywhere but the int8 wire codec, whose overflow/clip counts
    ride the metrics stream).

    ``sentinel=True`` (the guardrail layer, docs/RESILIENCE.md
    "Guardrails") adds the on-device health summary + guarded update
    (`_sentinel_tail`) and the ``guard_in`` third argument; off (the
    default) the body — and its compiled HLO — is bit-for-bit the program
    it always was.
    """

    finish = _make_update_tail(optimizer, schedule, reduce_fn, sentinel,
                               health_reduce, opt_pred_cast)

    def body(state: TrainState, batch, guard_in=None):
        # jax.named_scope: names land in HLO op metadata, so device-side
        # profiles (jax.profiler XPlane / Perfetto) attribute time to the
        # training phase instead of to anonymous fusions. Metadata only —
        # the compiled collective schedule (dplint DP304 fingerprint) is
        # unchanged.
        with jax.named_scope("tpu_dp.input"):
            images, labels = _inputs_and_labels(batch)
        if augment_fn is not None:
            # A phase of its own, beside and not inside the input's: an
            # op's name carries one phase. Keyed by the global step:
            # compiled into the program, deterministic, identical on
            # every replica.
            with jax.named_scope(_augment_phase(augment_fn)):
                images = augment_fn(state.step, images)
        with jax.named_scope("tpu_dp.fwd_bwd"):
            loss, grads, new_batch_stats, correct, counters = (
                _forward_backward(model, loss_impl, state, images, labels,
                                  cast_params=cast_params))
        count = jnp.asarray(labels.shape[0], jnp.int32)
        if sentinel:
            gi = guard_in if guard_in is not None else default_guard_in()
            loss, grads = _inject_guard_fault(state.step, loss, grads, gi)
        return finish(state, grads, new_batch_stats, loss, correct, count,
                      counters, guard_in)

    return body


def _make_accum_body(
    model, optimizer, schedule, loss_impl, augment_fn, accum_steps,
    reduce_fn=None, cast_params=None, sentinel=False, health_reduce=None,
    opt_pred_cast=None,
):
    """The gradient-accumulation step body: one optimizer update from
    ``accum_steps`` sequential microbatches.

    Batch leaves carry a leading (accum_steps,) axis (replicated; the
    microbatch dim is the sharded one). ``lax.scan`` runs the microbatches
    sequentially, accumulating grads on-device — how a logical global batch
    larger than HBM (e.g. BASELINE config 5's 4096) runs on few chips.
    One body for `make_train_step`'s single-batch feed (one dispatch per
    update) and its scanned feeds (scan-of-scan: a window of accumulated
    updates in one program), so the two cannot drift apart.
    """

    finish = _make_update_tail(optimizer, schedule, reduce_fn, sentinel,
                               health_reduce, opt_pred_cast)

    def body(state: TrainState, batch, guard_in=None):
        # Same named_scope annotations as `_make_step_body` (HLO metadata
        # for device-side trace attribution; schedule-neutral).
        with jax.named_scope("tpu_dp.input"):
            images, labels = _inputs_and_labels(batch)
        if augment_fn is not None:
            # On-device augmentation keyed by the global step and the
            # microbatch index: compiled into the step, deterministic,
            # identical on every replica.
            with jax.named_scope(_augment_phase(augment_fn)):
                images = jax.vmap(
                    lambda i, im: augment_fn(state.step * accum_steps + i, im)
                )(jnp.arange(accum_steps), images)

        # A model that publishes counters says which (`counter_names`);
        # their sums ride the carry beside the loss's.
        n_counters = len(getattr(model, "counter_names", ()))

        def micro(carry, mb):
            grads_acc, batch_stats, loss_acc, correct_acc, counters_acc = carry
            mstate = state.replace(batch_stats=batch_stats)
            with jax.named_scope("tpu_dp.fwd_bwd"):
                loss, grads, new_bs, correct, counters = _forward_backward(
                    model, loss_impl, mstate, mb["image"], mb["label"],
                    cast_params=cast_params,
                )
            grads_acc = jax.tree_util.tree_map(
                jnp.add, grads_acc, grads
            )
            if counters is not None:
                counters_acc = counters_acc + counters
            return (grads_acc, new_bs, loss_acc + loss,
                    correct_acc + correct, counters_acc), None

        init = (
            jax.tree_util.tree_map(jnp.zeros_like, state.params),
            state.batch_stats,
            jnp.zeros((), jnp.float32),
            jnp.zeros((), jnp.int32),
            jnp.zeros((n_counters,), jnp.float32) if n_counters else (),
        )
        if cast_params is not None:
            # Under shard_map a scan carry must enter with the type it
            # leaves with: per-shard grads, loss and correct come out
            # device-varying, and so do the running stats of a model whose
            # BatchNorm does not sync over the axis in its forward.
            vary = functools.partial(jax.tree_util.tree_map, cast_params)
            synced_bn = getattr(model, "axis_name", None) is not None
            init = (vary(init[0]),
                    init[1] if synced_bn else vary(init[1]),
                    vary(init[2]), vary(init[3]), vary(init[4]))
        (grads, new_batch_stats, loss_sum, correct, counters), _ = (
            jax.lax.scan(micro, init, {"image": images, "label": labels}))
        counters = counters if n_counters else None
        grads = jax.tree_util.tree_map(
            lambda g: g / accum_steps, grads
        )
        loss = loss_sum / accum_steps
        count = jnp.asarray(labels.shape[0] * labels.shape[1], jnp.int32)

        # The fault seam sits on the accumulated (whole-update) gradients,
        # like the reduce hook: one injected fault means one poisoned
        # optimizer update, never a per-microbatch spray.
        if sentinel:
            gi = guard_in if guard_in is not None else default_guard_in()
            loss, grads = _inject_guard_fault(state.step, loss, grads, gi)

        # The reduce hook sits AFTER the microbatch scan and the 1/accum
        # rescale: exactly one cross-replica reduction per optimizer update,
        # never one per microbatch (`tpu_dp.analysis` DP202 verifies this)
        # — and so the int8 codec quantizes (and its residual updates) once
        # per optimizer update too.
        return finish(state, grads, new_batch_stats, loss, correct, count,
                      counters, guard_in)

    return body


def _select_body(model, optimizer, schedule, loss_impl, augment_fn,
                 accum_steps, reduce_fn=None, cast_params=None,
                 sentinel=False, health_reduce=None, opt_pred_cast=None):
    """One source of truth for the per-update body: plain step at
    accum_steps == 1, gradient-accumulation body otherwise. Used by
    `make_train_step` and (via `make_local_step`) its explicit-collectives
    `shard_map` form, so all step programs share the exact same body."""
    if accum_steps == 1:
        return _make_step_body(model, optimizer, schedule, loss_impl,
                               augment_fn, reduce_fn=reduce_fn,
                               cast_params=cast_params, sentinel=sentinel,
                               health_reduce=health_reduce,
                               opt_pred_cast=opt_pred_cast)
    return _make_accum_body(model, optimizer, schedule, loss_impl,
                            augment_fn, accum_steps, reduce_fn=reduce_fn,
                            cast_params=cast_params, sentinel=sentinel,
                            health_reduce=health_reduce,
                            opt_pred_cast=opt_pred_cast)


def gather_rows(data, idx_step, sample_shapes):
    """One step's batch from the resident data set: rows ``idx_step`` of
    every array, then each row's own shape back (`DataPipeline.
    resident_data` stages rows flat, and says why). The reshape comes
    after the gather and inside its phase: the gathered batch is the
    array a 4-D staging gave, bit for bit, and the data set is never
    reshaped on the device."""
    with jax.named_scope("tpu_dp.gather"):
        return {
            k: x[idx_step].reshape(*idx_step.shape, *sample_shapes[k])
            for k, x in data.items()
        }


UPDATE_SHARDING_MODES = ("replicated", "sharded")


def _check_update_sharding(update_sharding: str, optimizer) -> None:
    """Fail fast on a mode/optimizer mismatch.

    The sharded layout is a *contract* between three parties — the reduce
    hook (flat grad shards out), the optimizer (`ShardedUpdate`: shard-
    shaped state, param-shard slicing, params all-gather), and the state
    created from that optimizer's `init`. A plain optimizer in sharded mode
    (or vice versa) would trace to shape errors deep inside the update;
    diagnose it at the factory boundary instead.
    """
    if update_sharding not in UPDATE_SHARDING_MODES:
        raise ValueError(
            f"update_sharding must be one of {UPDATE_SHARDING_MODES}, "
            f"got {update_sharding!r}"
        )
    is_sharded_opt = getattr(optimizer, "is_sharded_update", False)
    if update_sharding == "sharded" and not is_sharded_opt:
        raise ValueError(
            "update_sharding='sharded' requires a ShardedUpdate optimizer "
            "(train.optim.shard_optimizer) so the TrainState's opt_state "
            "was initialized in the sharded layout"
        )
    if update_sharding == "replicated" and is_sharded_opt:
        raise ValueError(
            "replicated update with a ShardedUpdate optimizer: the opt "
            "state layouts are incompatible; pass the inner optimizer"
        )


def _parse_wire_codec(collective_dtype: str | None,
                      quant_block_size: int | None = None,
                      quant_error_feedback: bool = True):
    """`train.collective_dtype` → wire codec for the gradient collective.

    The cast-only knob of PR 4 grown into a pluggable codec seam
    (`tpu_dp.parallel.quant.make_wire_codec`): None/"f32" keeps the leaf
    dtype on the wire, "bf16" returns the cast codec, "int8" the
    blockwise-absmax-scaled codec with error feedback — which is the one
    that needs the residual state threaded through `TrainState`.
    """
    from tpu_dp.parallel import quant

    return quant.make_wire_codec(
        collective_dtype,
        block_size=(quant.DEFAULT_BLOCK_SIZE if quant_block_size is None
                    else quant_block_size),
        error_feedback=quant_error_feedback,
    )


def _parse_exchange(
    optimizer,
    update_sharding: str,
    collective_dtype: str | None = None,
    quant_block_size: int | None = None,
    quant_error_feedback: bool = True,
    bucket_mb: float = 0.0,
):
    """The exchange keywords checked together: ``(codec, bucket_bytes)``.

    The wire codec and the bucketing both restructure the sharded update's
    explicit reduce-scatter; the replicated exchange (a pmean, or the
    all-reduce GSPMD infers) has nothing for them to act on, and a
    silently dropped keyword would leave the caller believing the
    compression or the overlap schedule armed. Refused here, for every
    program that trains.
    """
    from tpu_dp.parallel import bucketing

    _check_update_sharding(update_sharding, optimizer)
    codec = _parse_wire_codec(collective_dtype, quant_block_size,
                              quant_error_feedback)
    bucket_bytes = bucketing.parse_bucket_mb(bucket_mb)
    if update_sharding != "sharded":
        for name, armed in (("collective_dtype", codec is not None),
                            ("bucket_mb", bucket_bytes)):
            if armed:
                raise ValueError(
                    f"{name} applies to the sharded update's "
                    "reduce-scatter; pass update_sharding='sharded'"
                )
    return codec, bucket_bytes


def _state_specs(update_sharding: str):
    """PartitionSpec pytree-prefix for a TrainState under ``update_sharding``.

    Replicated mode: everything P() (one spec, prefix-matched). Sharded
    mode: opt_state leaves are flat 1-D arrays laid out over the data axis
    — P(DATA_AXIS) — while step/params/batch_stats stay replicated. The
    returned TrainState-of-specs is a pytree prefix (each field's spec
    broadcasts over that subtree), valid for shard_map in/out_specs and,
    mapped through NamedSharding, for jit in/out_shardings.
    """
    from jax.sharding import PartitionSpec as P

    from tpu_dp.parallel.dist import DATA_AXIS

    if update_sharding != "sharded":
        return P()
    # Residuals (int8 wire codec) are flat-sharded like the opt state:
    # f32[world, qpad] leaves with dim 0 over the data axis — each replica
    # holds its own pending-rounding-error row. {} when the codec is off,
    # where the prefix spec binds zero leaves.
    return TrainState(step=P(), params=P(), opt_state=P(DATA_AXIS),
                      batch_stats=P(), residuals=P(DATA_AXIS))


def _state_shardings(mesh: Mesh, update_sharding: str):
    """NamedSharding pytree-prefix for a TrainState (jit in/out_shardings):
    the device-placement twin of `_state_specs`."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from tpu_dp.parallel.dist import DATA_AXIS

    repl = replicated_sharding(mesh)
    if update_sharding != "sharded":
        return repl
    return TrainState(
        step=repl, params=repl,
        opt_state=NamedSharding(mesh, P(DATA_AXIS)),
        batch_stats=repl,
        residuals=NamedSharding(mesh, P(DATA_AXIS)),
    )


def make_local_step(
    model,
    optimizer: Optimizer,
    schedule: Schedule,
    use_pallas_xent: bool = False,
    accum_steps: int = 1,
    augment_fn: Callable | None = None,
    world: int = 1,
    axis_name: str | None = None,
    cast_params: bool = True,
    update_sharding: str = "replicated",
    collective_dtype: str | None = None,
    quant_block_size: int | None = None,
    quant_error_feedback: bool = True,
    bucket_mb: float = 0.0,
    sentinel: bool = False,
) -> Callable:
    """The per-shard step program with *explicit* collectives, unjitted.

    This is the SPMD program each device runs under
    `make_train_step(explicit=True)`: the shared step body (`_select_body` — the
    same normalize → augment → fwd/bwd → update the GSPMD path compiles)
    with the cross-replica reduction written out between the per-shard
    grads and the optimizer update — pmean(grads) / pmean(loss) /
    psum(correct) over the ``data`` axis via the typed wrappers in
    `tpu_dp.parallel.collectives`, a line-for-line statement of what DDP's
    C++ reducer fires from backward hooks.

    ``update_sharding="sharded"`` swaps the gradient pmean for the
    cross-replica sharded weight update (Xu et al., PAPERS.md): the reduce
    hook runs `collectives.psum_scatter` instead — each replica receives
    only the mean of its 1/world flat shard of every gradient leaf — and
    ``optimizer`` must be a `train.optim.ShardedUpdate`, whose update slices
    the matching parameter shards locally, steps 1/world of the state, and
    all-gathers the updated params. Same one-reduction-per-update invariant
    (`reduce_scatter` counts as the data-axis reduction for DP201/DP202);
    the compiled schedule becomes one reduce-scatter group + one all-gather
    group instead of one all-reduce group (DP301's second legal schedule).
    ``collective_dtype`` compresses the reduce-scatter wire format,
    EQuARX-style — off (None/"") reduces in the leaf dtype, "bf16" casts
    the payload, "int8" routes quantizable leaves through the blockwise-
    scaled codec (`collectives.psum_scatter_quant`): quantize once (with
    the ``TrainState.residuals`` error feedback, unless
    ``quant_error_feedback=False`` — the ablation seam), ONE int8
    all-to-all + f32 scales on the wire, dequantize-and-sum once; DP301's
    third legal schedule. ``quant_block_size`` sets the scaling-block
    length (`train.quant_block_size`; None = 256), and the step's metrics
    gain replicated ``quant_overflow``/``quant_clip`` block counts.

    Exposed as a factory (rather than a closure inside the shard_map
    wrapper) so `tpu_dp.analysis` can trace the *real shipped program* on
    abstract values and verify the reduction invariant — every gradient
    leaf reduced over the data axis exactly once per optimizer update,
    including under gradient accumulation (`accum_steps > 1`, where the
    reduction must sit after the microbatch scan, not inside it).

    ``bucket_mb > 0`` (`train.bucket_mb`, docs/PERF.md "Overlapped
    collectives") issues the gradient exchange as K size-targeted bucket
    reductions in reverse production order instead of one monolithic
    reduce-scatter — `collectives.psum_scatter_bucketed` (f32/bf16 wire)
    or `psum_scatter_quant_bucketed` (int8, per-bucket error-feedback
    residuals) — with `optimization_barrier` anti-combining hints so XLA's
    latency-hiding scheduler can overlap each bucket's wire time with the
    remaining backward compute. Sharded mode only (the overlap schedule
    IS the decomposed exchange); DP301 verifies the K-bucket schedule
    covers the union of gradient leaves exactly once.

    ``cast_params=False`` skips the varying-cast of the params; the
    analyzer uses it to trace outside a real `shard_map` scope.
    """
    from tpu_dp.parallel import collectives, quant
    from tpu_dp.parallel.dist import DATA_AXIS

    if axis_name is None:
        axis_name = DATA_AXIS
    codec, bucket_bytes = _parse_exchange(
        optimizer, update_sharding, collective_dtype, quant_block_size,
        quant_error_feedback, bucket_mb)

    loss_impl = _select_loss_impl(use_pallas_xent)

    def reduce_fn(grads, loss, correct, count, batch_stats, residuals,
                  counters=None):
        # The explicit DDP reduction: grad mean over the data axis, exactly
        # once, after any gradient-accumulation scan. Replicated mode
        # all-reduces the full leaves; sharded mode reduce-scatters, each
        # replica keeping only the shard its optimizer slice will consume —
        # through the int8 wire codec when configured (quantize once →
        # int8 all-to-all → dequantize once; residuals carry the error
        # feedback across steps), and as K bucketed reductions in reverse
        # production order when `bucket_mb` arms the overlap schedule.
        extra = {}
        if isinstance(codec, quant.Int8BlockCodec):
            if bucket_bytes:
                grads, residuals, stats = (
                    collectives.psum_scatter_quant_bucketed(
                        grads, residuals, axis_name, world=world, mean=True,
                        block_size=codec.block_size,
                        error_feedback=codec.error_feedback,
                        bucket_bytes=bucket_bytes,
                    ))
            else:
                grads, residuals, stats = collectives.psum_scatter_quant(
                    grads, residuals, axis_name, world=world, mean=True,
                    block_size=codec.block_size,
                    error_feedback=codec.error_feedback,
                )
            # Codec-health counts are rank-local (each replica quantizes
            # its own contribution): two scalar psums make them replicated
            # metrics — declared in the analyzer's metric-reduction budget
            # for the int8 programs, like loss/correct.
            extra = {
                "quant_overflow": collectives.psum(
                    stats["overflow"], axis_name),
                "quant_clip": collectives.psum(stats["clip"], axis_name),
            }
        elif bucket_bytes:
            grads = collectives.psum_scatter_bucketed(
                grads, axis_name, world=world, mean=True,
                dtype=codec.dtype if codec is not None else None,
                bucket_bytes=bucket_bytes,
            )
        elif update_sharding == "sharded":
            grads = collectives.psum_scatter(
                grads, axis_name, world=world, mean=True,
                dtype=codec.dtype if codec is not None else None,
            )
        else:
            grads = collectives.pmean(grads, axis_name)
        if counters is not None:
            # A model's own counters are per-shard sums: one more psum,
            # in programs of such a model only.
            extra["counters"] = collectives.psum(counters, axis_name)
        loss = collectives.pmean(loss, axis_name)
        correct = collectives.psum(correct, axis_name)
        count = count * world
        if getattr(model, "axis_name", None) is None and batch_stats:
            # Unsynced BN model: average per-shard running stats so state
            # leaves shard_map replicated. Models built with
            # axis_name=DATA_AXIS already synced in-forward — skip the
            # redundant per-step all-reduce over the stats tree.
            batch_stats = collectives.pmean(batch_stats, axis_name)
        return grads, loss, correct, count, batch_stats, residuals, extra

    # Mark the replicated params as device-varying before differentiating.
    # Under shard_map's replication typing, grads of a *varying* loss wrt
    # *invariant* params would get an implicit cross-shard psum inserted
    # by AD (the cotangent of the invariant→varying broadcast) — i.e.
    # globally-summed grads before our explicit collective, which would
    # overscale the update by the world size. Casting params to
    # *varying* keeps AD local: per-shard grads out, exactly what DDP's
    # reducer sees pre-allreduce.
    cast = (lambda p: _to_varying(p, axis_name)) if cast_params else None
    # The sentinel's cross-replica gap on the sharded path: each replica
    # holds a 1/world gradient shard, so the health sum-of-squares needs
    # one scalar psum (the ONLY collective the sentinel adds — the
    # replicated path computes it on already-pmean'ed grads), and the
    # skip select over the varying opt-state shards needs a varying
    # predicate under replication typing.
    health_reduce = None
    opt_pred_cast = None
    if sentinel and update_sharding == "sharded":
        health_reduce = lambda s: collectives.psum(s, axis_name)  # noqa: E731
        if cast_params:
            opt_pred_cast = lambda p: _to_varying(p, axis_name)  # noqa: E731
    return _select_body(model, optimizer, schedule, loss_impl, augment_fn,
                        accum_steps, reduce_fn=reduce_fn, cast_params=cast,
                        sentinel=sentinel, health_reduce=health_reduce,
                        opt_pred_cast=opt_pred_cast)


FEEDS = ("batch", "window", "resident")


def make_train_step(
    model,
    optimizer: Optimizer,
    mesh: Mesh,
    schedule: Schedule,
    use_pallas_xent: bool = False,
    accum_steps: int = 1,
    augment_fn: Callable | None = None,
    sentinel: bool = False,
    *,
    feed: str = "batch",
    num_steps: int = 1,
    sample_shapes: dict[str, tuple[int, ...]] | None = None,
    update_sharding: str = "replicated",
    collective_dtype: str | None = None,
    quant_block_size: int | None = None,
    quant_error_feedback: bool = True,
    bucket_mb: float = 0.0,
    explicit: bool | None = None,
) -> Callable:
    """Build the jitted DP train program for this model/optimizer/mesh:
    the one factory of every program that trains.

    Every program runs the same per-update body (`_select_body`; with
    ``accum_steps > 1`` one update from that many microbatches, the fed
    leaves gaining a leading replicated (accum_steps,) axis), donates its
    state, and returns ``(new_state, metrics)``: mean loss, correct count,
    example count and lr as replicated scalars — what the reference prints
    (`cifar_example.py:83-87`) and its synced eval metric accumulates
    (`cifar_example_ddp.py:133`). ``feed`` says what a call is handed and
    how many steps it runs:

    - ``"batch"``: ``step(state, batch)``, the device-placed global batch
      (leading dim sharded over ``data``), one step.
    - ``"window"``: ``loop(state, batches)``, ``num_steps`` steps in one
      dispatch (the reference's eager loop pays a launch every step,
      `/root/reference/cifar_example_ddp.py:94-107`): a ``lax.scan`` over
      batches with the scan axis in front, (pool, [accum_steps,]
      global_batch, ...). A pool smaller than ``num_steps`` is cycled
      modularly inside the program, so HBM cost stays constant in
      ``num_steps``.
    - ``"resident"``: ``loop(state, data, idx)``: ``data`` is
      `DataPipeline.resident_data()`, the train set staged in HBM once
      (replicated, rows flat — that method says why — and not donated),
      ``idx`` int32 indices with the scan axis in front: ~KBs a dispatch
      where the reference's DataLoader ships the batch
      (`/root/reference/cifar_example.py:46-52`). Each scanned step gathers
      its batch on the device (`gather_rows`: the indices are sharded, so
      every device gathers its own examples) and ``sample_shapes``
      (`DataPipeline.sample_shapes`) gives each row its shape back.

    A program of several steps returns its metrics stacked by step; one of
    ``num_steps == 1`` returns that step's own, whatever the feed, so its
    caller never indexes a stack of one (outside the program ``v[0]`` is a
    device program of its own, launched between two steps). All three
    feeds follow one trajectory (equivalence-tested).

    The exchange. By default GSPMD *infers* the gradient all-reduce from
    the shardings (batch sharded, state replicated, the loss a mean over
    the logical batch). ``explicit=True`` states the same program per shard
    under `shard_map` with the collectives written out (`make_local_step`,
    which documents ``update_sharding``, ``collective_dtype``,
    ``quant_block_size``, ``quant_error_feedback`` and ``bucket_mb``): what
    DDP's C++ reducer does from backward hooks
    (`/root/reference/cifar_example_ddp.py:83`), equivalence-tested against
    the inferred form, and the extension point for hand-scheduled comms.
    ``update_sharding="sharded"`` is that point used (``optimizer`` a
    `train.optim.ShardedUpdate`, the opt state sharded over ``data``), and
    ``explicit=None`` means explicit exactly then. BatchNorm models under
    explicit collectives are built with ``axis_name=DATA_AXIS`` (sync-BN:
    the global-batch statistics GSPMD computes by itself). Replication
    checking stays on: a rank-varying output (a forgotten pmean on a new
    metric) is a trace-time error, not device 0's answer.

    ``sentinel=True`` (guard.enabled, docs/RESILIENCE.md "Guardrails")
    compiles the on-device health summary and the guarded update in: the
    program takes a last argument ``guard_in`` (`default_guard_in`:
    replicated scalars, not donated, one for every step of a window, since
    the host observes window boundaries only) and metrics gain
    ``loss_raw`` / ``grad_norm`` / ``applied``. A step that trips the guard
    emits its state unchanged, and a window's remaining steps go on from
    there. Off, the compiled HLO is the pre-guardrails program (the DP304
    fingerprint is digest-identical).
    """
    from jax.sharding import NamedSharding, PartitionSpec as P

    from tpu_dp.parallel.dist import DATA_AXIS, data_axis_size

    if feed not in FEEDS:
        raise ValueError(f"feed must be one of {FEEDS}, got {feed!r}")
    if feed == "batch" and num_steps != 1:
        raise ValueError(
            f"feed='batch' runs one step a call, got num_steps={num_steps}; "
            "a window is feed='window' or feed='resident'"
        )
    if feed == "resident" and sample_shapes is None:
        raise ValueError(
            "feed='resident' needs sample_shapes "
            "(DataPipeline.sample_shapes): the staged rows are flat"
        )
    if explicit is None:
        explicit = update_sharding == "sharded"
    exchange = dict(
        update_sharding=update_sharding, collective_dtype=collective_dtype,
        quant_block_size=quant_block_size,
        quant_error_feedback=quant_error_feedback, bucket_mb=bucket_mb,
    )
    if explicit:
        body = make_local_step(
            model, optimizer, schedule, use_pallas_xent=use_pallas_xent,
            accum_steps=accum_steps, augment_fn=augment_fn,
            world=data_axis_size(mesh), axis_name=DATA_AXIS,
            sentinel=sentinel, **exchange,
        )
    else:
        _parse_exchange(optimizer, **exchange)
        if update_sharding == "sharded":
            raise ValueError(
                "update_sharding='sharded' needs explicit collectives; "
                "GSPMD infers the replicated all-reduce only"
            )
        body = _select_body(model, optimizer, schedule,
                            _select_loss_impl(use_pallas_xent), augment_fn,
                            accum_steps, sentinel=sentinel)

    # One window's guard_in serves each of its steps.
    def per_step(guard_in):
        if guard_in is None:
            return body
        return lambda st, mb: body(st, mb, guard_in)

    def scan(step_body, state, xs):
        state, metrics = jax.lax.scan(step_body, state, xs, length=num_steps)
        if num_steps == 1:  # the step's own metrics, indexed where it is free
            metrics = jax.tree_util.tree_map(lambda v: v[0], metrics)
        return state, metrics

    if feed == "batch":
        run = body
    elif feed == "window":
        def loop(state: TrainState, batches, guard_in=None):
            step_body = per_step(guard_in)
            pool = jax.tree_util.tree_leaves(batches)[0].shape[0]
            if pool == num_steps:
                return scan(step_body, state, batches)

            def indexed_body(st, i):
                mb = jax.tree_util.tree_map(
                    lambda x: jax.lax.dynamic_index_in_dim(
                        x, i % pool, keepdims=False
                    ),
                    batches,
                )
                return step_body(st, mb)

            return scan(indexed_body, state,
                        jnp.arange(num_steps, dtype=jnp.int32))

        run = loop
    else:
        def loop(state: TrainState, data, idx, guard_in=None):
            step_body = per_step(guard_in)

            def indexed_body(st, idx_step):
                return step_body(st, gather_rows(data, idx_step,
                                                 sample_shapes))

            return scan(indexed_body, state, idx)

        run = loop

    # What a call is handed after the state: the staged data set whole on
    # every device, then the fed leaves with the scan axis and the
    # microbatch-stack axis (where there is one) in front and the batch dim
    # sharded over data, then the guard's scalars.
    prefix_dims = (feed != "batch") + (accum_steps > 1)
    fed = [P()] if feed == "resident" else []
    fed.append(P(*([None] * prefix_dims), DATA_AXIS))
    if sentinel:
        fed.append(P())
    state_sh = _state_shardings(mesh, update_sharding)
    if explicit:
        state_spec = _state_specs(update_sharding)
        run = _shard_map(run, mesh=mesh, in_specs=(state_spec, *fed),
                         out_specs=(state_spec, P()))
    return jax.jit(
        run,
        in_shardings=(state_sh,
                      *(NamedSharding(mesh, spec) for spec in fed)),
        out_shardings=(state_sh, replicated_sharding(mesh)),
        donate_argnums=(0,),
    )


def _infer_forward(model, state: TrainState, batch):
    """Shared inference forward: normalize → model(train=False) → logits/preds.

    One source of truth for the two inference consumers — `make_eval_step`
    (training-time accuracy) and `make_serve_step` (the serving subsystem,
    `tpu_dp/serve/`) — so the serve path can never drift from the forward
    the eval metrics were measured on. Uses running statistics for
    BatchNorm models; ``state`` only needs params/batch_stats populated
    (serve passes a TrainState with an empty opt_state).
    """
    logits = _infer_outputs(model, state, batch)
    predictions = jnp.argmax(logits, axis=-1)
    return logits, predictions


def _infer_outputs(model, state: TrainState, batch, input_fn=None):
    """The model's outputs on a batch at ``train=False``: logits, or a
    `RowLoss` from a model that computes its own. ``input_fn(inputs)`` is
    the part of the training step's input seam that the objective needs at
    evaluation too (the diffusion noise, on a fixed key)."""
    inputs, _ = _inputs_and_labels(batch)
    if input_fn is not None:
        with jax.named_scope(_augment_phase(input_fn)):
            inputs = input_fn(inputs)
    return _apply_model(model, state, inputs, train=False)[0]


def make_eval_step(model, mesh: Mesh,
                   update_sharding: str = "replicated",
                   input_fn: Callable | None = None) -> Callable:
    """Build the jitted eval step: global (correct, count) per batch.

    A model that returns a `RowLoss` (`tpu_dp.models.outputs`) reports its
    own loss, with ``correct`` and ``count`` the judged items predicted
    right and their number, in the same three slots.

    ``update_sharding`` must match the TrainState's layout: with the
    sharded weight update the opt_state leaves arrive sharded over ``data``
    (the eval computation never touches them, but jit checks every input's
    declared sharding against the committed buffers).

    Parity with the reference's synced eval
    (`cifar_example_ddp.py:124-136`): torchmetrics allreduces
    correct/total state on every update (`dist_sync_on_step=True`). Here each
    batch's counts are computed over the *sharded global* batch, so the
    cross-chip reduction is inside the compiled step and the returned scalars
    are already exact global values — same semantics, one fused collective.
    Uses running statistics for BatchNorm models (`train=False`); the
    reference never calls `.eval()` (`cifar_example_ddp.py:130` — moot for
    its BN-free `Net`, divergence documented per SURVEY.md §3.4).
    """
    repl = replicated_sharding(mesh)
    batch_sh = batch_sharding(mesh)
    state_sh = _state_shardings(mesh, update_sharding)

    def step(state: TrainState, batch):
        weight = batch.get("weight")
        outputs = _infer_outputs(model, state, batch, input_fn)
        if isinstance(outputs, RowLoss):
            w = jnp.ones_like(outputs.loss) if weight is None else weight
            return {
                "loss": jnp.sum(outputs.loss * w) / jnp.maximum(jnp.sum(w), 1.0),
                "correct": jnp.sum(outputs.correct * w).astype(jnp.int32),
                "count": jnp.sum(outputs.count * w).astype(jnp.int32),
            }
        labels = batch["label"]
        logits, predictions = outputs, jnp.argmax(outputs, axis=-1)
        if weight is None:
            correct = jnp.sum(predictions == labels)
            count = jnp.asarray(labels.shape[0], jnp.int32)
        else:
            correct = jnp.sum((predictions == labels) * weight).astype(jnp.int32)
            count = jnp.sum(weight).astype(jnp.int32)
        return {
            "loss": cross_entropy_loss(logits, labels, weight),
            "correct": correct,
            "count": count,
        }

    return jax.jit(
        step,
        in_shardings=(state_sh, batch_sh),
        out_shardings=repl,
    )


def init_serve_stats(num_classes: int):
    """Device-resident serving statistics threaded through every serve step.

    ``served`` counts examples actually served (padding excluded via the
    batch's weight mask) and ``class_counts`` is the per-class prediction
    histogram — the device-side ground truth `tpu_dp.serve` cross-checks
    its host-side request counters against. This pytree is the serve
    step's *donated* argument: like the train state, it is consumed and
    re-emitted every call, so XLA aliases the buffers in place (dplint
    DP303 verifies the aliasing for the serve programs too) and the
    dispatch loop never churns the allocator.
    """
    return {
        "served": jnp.zeros((), jnp.int32),
        "class_counts": jnp.zeros((int(num_classes),), jnp.int32),
    }


def make_serve_step(model, mesh: Mesh, batch_size: int) -> Callable:
    """Compiled donated-buffer inference forward for ONE padded bucket size.

    The serving hot path (`tpu_dp/serve/engine.py`) keeps the training
    stack's compiled-program discipline: every batch the dynamic batcher
    forms is padded to a fixed bucket size from a ladder, and each bucket
    gets exactly one program built by this factory — fixed shapes, stats
    donation, a fingerprinted collective schedule (registered in dplint's
    Level-3 artifact) — so after one warmup call per bucket the
    RecompileGuard must observe zero retraces.

    Returns ``step(stats, state, batch) -> (new_stats, out)`` where:

    - ``stats`` is `init_serve_stats`'s pytree, **donated** (argnum 0 —
      the leading flattened leaves, which is what DP303's prefix check
      verifies); ``new_stats`` aliases its buffers;
    - ``state`` is a `TrainState` whose params/batch_stats are populated
      (opt_state may be empty — serving never materializes it; see
      `checkpoint.load_params_only`), replicated and NOT donated: it is
      reused by every call of every bucket program;
    - ``batch`` is ``{"image": [B, H, W, C], "weight": f32[B]}`` with
      ``weight`` masking padded rows out of the stats (1.0 = real
      example), and ``out`` is ``{"prediction": s32[B],
      "confidence": f32[B]}`` (top-1 class and its softmax probability).

    Replica fan-out comes from the data mesh for free: buckets divisible
    by the data-axis size shard the batch (and the per-example outputs)
    over ``data`` — each replica runs B/world examples and the only
    collectives are the two stats reductions (one scalar, one [C]-vector
    all-reduce, full-mesh, add — the schedule DP301 holds serve programs
    to). Smaller buckets run replicated (every device computes the whole
    batch — duplicated work is cheaper than a resharding collective at
    those sizes), compiling to zero collectives.
    """
    repl = replicated_sharding(mesh)
    from tpu_dp.parallel.dist import data_axis_size

    if batch_size < 1:
        raise ValueError(f"batch_size must be positive, got {batch_size}")
    sharded = batch_size % data_axis_size(mesh) == 0
    batch_sh = batch_sharding(mesh) if sharded else repl

    def step(stats, state: TrainState, batch):
        logits, predictions = _infer_forward(model, state, batch)
        weight = batch["weight"]
        probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
        confidence = jnp.max(probs, axis=-1)
        one_hot = jax.nn.one_hot(
            predictions, logits.shape[-1], dtype=jnp.float32
        )
        new_stats = {
            "served": stats["served"]
            + jnp.sum(weight).astype(jnp.int32),
            "class_counts": stats["class_counts"]
            + jnp.sum(one_hot * weight[:, None], axis=0).astype(jnp.int32),
        }
        out = {
            "prediction": predictions.astype(jnp.int32),
            "confidence": confidence,
        }
        return new_stats, out

    return jax.jit(
        step,
        in_shardings=(repl, repl, batch_sh),
        out_shardings=(repl, batch_sh),
        donate_argnums=(0,),
    )

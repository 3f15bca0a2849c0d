"""SGD with momentum — exact update-rule parity with the reference.

The reference uses `optim.SGD(lr=0.001, momentum=0.9)` with no weight decay,
no dampening, no Nesterov (`/root/reference/cifar_example.py:64`,
`cifar_example_ddp.py:86`). Torch's update rule (which differs from the
classical velocity form) is:

    buf ← momentum·buf + grad          (buf starts as grad on step 0)
    p   ← p − lr·buf

Implemented here as a pure pytree transform (buffers zero-initialized:
momentum·0 + grad == grad on step 0, identical trajectory). Weight decay, when
enabled for the ResNet presets, is torch-style decoupled-from-schedule L2:
grad ← grad + wd·p before the momentum accumulation.

The learning rate is a traced scalar input, so LR schedules (BASELINE.json
config 5's cosine) change no compiled code.

`ShardedUpdate` wraps any such pytree optimizer into the cross-replica
*sharded* weight update of Xu et al. (PAPERS.md, `train.update_sharding=
sharded`): the step hands it reduce-scattered gradient shards, it slices the
matching 1/world parameter shards locally, runs the wrapped update on 1/world
of every leaf, and all-gathers only the updated parameters — optimizer state
(momentum, and any future slots) lives permanently sharded over the data
axis, cutting its per-replica memory to ~1/world and the update FLOPs with
it.
"""

from __future__ import annotations

import functools
from typing import Any, Protocol

import jax
import jax.numpy as jnp


class Optimizer(Protocol):
    def init(self, params) -> Any: ...
    def update(self, grads, opt_state, params, lr) -> tuple[Any, Any]: ...


def _is_no_decay_leaf(path) -> bool:
    """True for leaves conventionally excluded from weight decay: biases and
    normalization scales (BatchNorm parameters are named scale/bias in Flax;
    Dense/Conv biases are named bias). Matches the common high-accuracy
    ResNet recipe; torch's SGD decays everything, which stays the default."""
    last = path[-1]
    name = getattr(last, "key", getattr(last, "name", str(last)))
    return name in ("bias", "scale")


class SGD:
    """Torch-semantics SGD(momentum) as a stateless pytree transform."""

    def __init__(
        self,
        momentum: float = 0.9,
        weight_decay: float = 0.0,
        decay_exclude_bias_and_norm: bool = False,
    ):
        self.momentum = momentum
        self.weight_decay = weight_decay
        self.decay_exclude_bias_and_norm = decay_exclude_bias_and_norm

    def init(self, params):
        if self.momentum == 0.0:
            return ()
        return jax.tree_util.tree_map(jnp.zeros_like, params)

    def update(self, grads, opt_state, params, lr):
        """Returns (new_params, new_opt_state)."""
        if self.weight_decay:
            if self.decay_exclude_bias_and_norm:
                grads = jax.tree_util.tree_map_with_path(
                    lambda path, g, p: g
                    if _is_no_decay_leaf(path)
                    else g + self.weight_decay * p,
                    grads,
                    params,
                )
            else:
                grads = jax.tree_util.tree_map(
                    lambda g, p: g + self.weight_decay * p, grads, params
                )
        if self.momentum == 0.0:
            new_params = jax.tree_util.tree_map(
                lambda p, g: p - lr * g, params, grads
            )
            return new_params, opt_state
        new_buf = jax.tree_util.tree_map(
            lambda b, g: self.momentum * b + g, opt_state, grads
        )
        new_params = jax.tree_util.tree_map(
            lambda p, b: p - lr * b, params, new_buf
        )
        return new_params, new_buf


class AdamW:
    """AdamW as a pytree transform, through the same protocol as `SGD`.

    Bias-corrected moments ``m``, ``v`` (``b1``, ``b2``, ``eps``), decoupled
    decay ``p -= lr * wd * p`` (on every leaf, or sparing biases and norm
    scales with ``decay_exclude_bias_and_norm``), and the gradient clipped
    to a global norm of ``clip_norm`` before the moments (0 = no clipping).
    The state is ``{"count", "m", "v"}``: with float32 parameters and
    gradients, 16 bytes a parameter in all.

    ``sumsq_reduce`` closes the cross-replica gap of the clip under
    `ShardedUpdate`, where each replica holds 1/world of every gradient:
    the local sum of squares is partial, and one scalar psum makes it the
    global norm's (`ShardedUpdate.update` passes it).
    """

    def __init__(self, b1: float = 0.9, b2: float = 0.95, eps: float = 1e-8,
                 weight_decay: float = 0.0, clip_norm: float = 0.0,
                 decay_exclude_bias_and_norm: bool = False):
        self.b1, self.b2, self.eps = b1, b2, eps
        self.weight_decay = weight_decay
        self.clip_norm = clip_norm
        self.decay_exclude_bias_and_norm = decay_exclude_bias_and_norm

    def init(self, params):
        zeros = lambda: jax.tree_util.tree_map(jnp.zeros_like, params)  # noqa: E731
        return {"count": jnp.zeros((), jnp.int32), "m": zeros(), "v": zeros()}

    def update(self, grads, opt_state, params, lr, sumsq_reduce=None):
        """Returns (new_params, new_opt_state)."""
        if self.clip_norm:
            sumsq = sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                        for g in jax.tree_util.tree_leaves(grads))
            if sumsq_reduce is not None:
                sumsq = sumsq_reduce(sumsq)
            scale = jnp.minimum(1.0, self.clip_norm / (jnp.sqrt(sumsq) + 1e-6))
            grads = jax.tree_util.tree_map(lambda g: g * scale, grads)
        b1, b2 = self.b1, self.b2
        count = opt_state["count"] + 1
        c1 = 1.0 - b1 ** count.astype(jnp.float32)
        c2 = 1.0 - b2 ** count.astype(jnp.float32)
        m = jax.tree_util.tree_map(
            lambda m, g: b1 * m + (1.0 - b1) * g, opt_state["m"], grads)
        v = jax.tree_util.tree_map(
            lambda v, g: b2 * v + (1.0 - b2) * g * g, opt_state["v"], grads)

        def leaf(path, p, m, v):
            step = (m / c1) / (jnp.sqrt(v / c2) + self.eps)
            spared = (self.decay_exclude_bias_and_norm
                      and _is_no_decay_leaf(path))
            if self.weight_decay and not spared:
                step = step + self.weight_decay * p
            return p - lr * step

        new_params = jax.tree_util.tree_map_with_path(leaf, params, m, v)
        return new_params, {"count": count, "m": m, "v": v}


class ShardedUpdate:
    """Cross-replica sharded weight update over ``axis_name`` (Xu et al.).

    Wraps a pytree optimizer so the update runs on 1/world of every leaf:

        grad shards (from `collectives.psum_scatter`, flat 1-D)
          + param shards (local `collectives.shard_slice`, no comms)
          → inner.update on the shards
          → `collectives.all_gather` of the updated params only.

    Contract with the step factories (`train.step`): the gradients handed to
    ``update`` are *already* reduce-scattered flat shards — the reduce hook
    in `make_local_step(update_sharding="sharded")` produced them — while
    ``params`` are the full replicated leaves. ``opt_state`` is created by
    this class's ``init`` and is permanently shard-laid-out: each leaf is
    flat 1-D of `padded_size(n, world)` elements globally, sharded over the
    data axis (per-replica view inside `shard_map`: `shard_size(n, world)`
    elements — ~1/world of the replicated layout's memory).

    Weight decay and the decay-exclusion mask live in the wrapped optimizer
    and work unchanged: the shard trees preserve the param tree structure
    (`tree_map_with_path` sees the same key paths), and decay's
    ``g + wd·p`` is elementwise, so shard-wise == full-tensor.
    """

    is_sharded_update = True  # step-factory handshake (duck-typed)

    def __init__(self, inner: "Optimizer", world: int,
                 axis_name: str | None = None):
        from tpu_dp.parallel.dist import DATA_AXIS

        if world < 1:
            raise ValueError(f"world must be >= 1, got {world}")
        self.inner = inner
        self.world = int(world)
        self.axis_name = DATA_AXIS if axis_name is None else axis_name

    def init(self, params):
        """Shard-laid-out optimizer state: global view, host-side.

        Each inner-state leaf becomes flat 1-D of `padded_size(n, world)`
        zeros; jit's ``in_shardings`` (P over the data axis) slices it to
        `shard_size(n, world)` per replica. Runs on host (no axis bound), so
        it builds the *global* layout the per-shard program's out_specs
        stitch back together.
        """
        from tpu_dp.parallel.collectives import padded_size

        inner_state = self.inner.init(params)
        return jax.tree_util.tree_map(
            lambda s: jnp.zeros((padded_size(s.size, self.world),), s.dtype),
            inner_state,
        )

    def local_view(self, opt_state):
        """Per-replica slice of a global-layout ``opt_state`` (leaf[:n/w]).

        What one replica sees inside `shard_map` — used by the analyzers to
        trace the per-shard program outside a real shard_map scope, and by
        tests asserting the ~1/world memory claim.
        """
        return jax.tree_util.tree_map(
            lambda s: s[: s.size // self.world], opt_state
        )

    def update(self, grad_shards, opt_state, params, lr):
        """Per-shard update; returns (full new_params, sharded new state)."""
        from tpu_dp.parallel import collectives

        param_shards = collectives.shard_slice(
            params, self.axis_name, world=self.world
        )
        # A scalar slot (Adam's step count) is kept one copy a replica. A
        # checkpoint relayout pads with zeros, so after a restore onto more
        # replicas, or from the replicated layout, some copies are zero:
        # take the largest. (SGD's state has no scalar and gets no pmax.)
        scalars = jax.tree_util.tree_map(
            lambda s: s.ndim == 0, jax.eval_shape(self.inner.init, params))
        opt_state = jax.tree_util.tree_map(
            lambda s, scalar: jax.lax.pcast(
                collectives.pmax(s, self.axis_name), self.axis_name,
                to="varying") if scalar else s,
            opt_state, scalars)
        extra = {}
        if getattr(self.inner, "clip_norm", 0.0):
            # A global gradient norm over shards: one scalar psum.
            extra["sumsq_reduce"] = functools.partial(
                collectives.psum, axis_name=self.axis_name)
        new_param_shards, new_opt_state = self.inner.update(
            grad_shards, opt_state, param_shards, lr, **extra
        )
        new_params = collectives.all_gather(
            new_param_shards, params, self.axis_name
        )
        return new_params, new_opt_state


def shard_optimizer(optimizer: "Optimizer", world: int,
                    axis_name: str | None = None) -> ShardedUpdate:
    """`ShardedUpdate` over ``optimizer``. World 1 is the same code path
    with degenerate (1-replica) collectives — one layout everywhere, so a
    sharded checkpoint written on one topology restores on any other."""
    return ShardedUpdate(optimizer, world, axis_name)

"""Level-2 dplint: the jaxpr gradient-sync verifier (DP201–DP203).

The data-parallel contract the whole framework rests on is numeric, not
lexical: every parameter leaf's gradient must be all-reduced over the
``data`` mesh axis *exactly once* per optimizer update. Zero reductions
(DP201) trains each replica on its own shard and the replicas silently
diverge; two reductions (DP202 — the classic bug is one pmean per
microbatch plus one per update under gradient accumulation) silently
rescales the update; an unknown axis name (DP203) fails only when the full
program finally traces on a real mesh.

This pass checks the contract on the *real shipped program*: it traces the
per-shard step `tpu_dp.train.step.make_local_step` builds (the exact body
`make_train_step(explicit=True)` wraps) on abstract values with the data axis
bound, then walks the jaxpr backward from each updated-parameter output.
Because the SGD update is an independent per-leaf dataflow, the backward
slice of one parameter output contains precisely the collectives that
touched that parameter's gradient — so the reduction count is exact per
leaf, and reductions placed inside a `lax.scan` (per-microbatch — the
accumulation bug) are weighted by the scan trip count.

The GSPMD `jit` path shares the same body with the reduction inferred by
the partitioner rather than written out, so verifying the explicit program
verifies the shared body's reduction placement for both.
"""

from __future__ import annotations

import re
from typing import Any, Callable, Sequence

from tpu_dp.analysis.report import Finding

# Primitives that reduce over a named mesh axis. `lax.pmean` traces as
# psum-then-div, so psum covers both; pmin/pmax are not gradient
# reductions but still cross-replica syncs worth counting on a grad path.
# `reduce_scatter` (lax.psum_scatter) is the sharded weight update's
# gradient reduction (`train.update_sharding=sharded`): each replica
# receives the data-axis sum of its shard — reduced exactly once, like
# psum, just not everywhere. The params all-gather that follows the
# sharded update is NOT a reduction and is deliberately absent here.
_REDUCTION_PRIMS = {"psum", "pmin", "pmax", "psum_invariant", "reduce_scatter"}

# The int8 wire codec (`train.collective_dtype=int8`,
# `parallel/collectives.py psum_scatter_quant`) carries the gradient
# reduction as a quantized exchange: ONE int8 `all_to_all` (the payload —
# each replica then dequantizes and locally sums the world chunks it
# received; the local reduce_sum is the reduction's arithmetic, the
# all_to_all is its data-axis leg). An all_to_all is NOT a reduction in
# general — only the **int8-typed** exchange on a gradient's backward
# slice counts as that leaf's data-axis reduction. The f32 *scales*
# all_to_all riding alongside is wire metadata, deliberately not counted
# (same status as the params all-gather above): counting it would make
# every quantized leaf read as twice-reduced (a false DP202) while a real
# double reduction — two int8 exchanges, or an int8 exchange plus a psum
# — still fires.
_QUANT_WIRE_PRIM = "all_to_all"


def _is_quant_wire_reduction(eqn) -> bool:
    """True when ``eqn`` is the int8 payload exchange of the quantized
    reduce-scatter (int8-typed all_to_all; f32 scales don't count)."""
    if eqn.primitive.name != _QUANT_WIRE_PRIM:
        return False
    import numpy as np

    try:
        dtype = eqn.invars[0].aval.dtype
    except (AttributeError, IndexError):
        return False
    return dtype == np.int8

_PARAM_KEY = re.compile(r"\bparams\b")


def _keystr(path) -> str:
    import jax

    return jax.tree_util.keystr(path)


def _sub_jaxprs(eqn) -> list[tuple[Any, int | None]]:
    """(closed_jaxpr, trip_multiplier) pairs nested in an eqn.

    ``trip_multiplier`` is the scan length when statically known, 1 for
    plain call-like primitives, and None for loops with unknown trip count
    (a reduction there runs "at least twice" for counting purposes).
    """
    import jax.extend.core as core

    out: list[tuple[Any, int | None]] = []
    name = eqn.primitive.name
    if name == "scan":
        out.append((eqn.params["jaxpr"], int(eqn.params.get("length", 0)) or None))
        return out
    if name == "while":
        out.append((eqn.params["body_jaxpr"], None))
        return out
    for val in eqn.params.values():
        if isinstance(val, core.ClosedJaxpr):
            out.append((val, 1))
        elif isinstance(val, (tuple, list)):
            for item in val:
                if isinstance(item, core.ClosedJaxpr):
                    out.append((item, 1))
    return out


def _count_reductions(jaxpr, target_outvars, axis: str) -> int:
    """Data-axis reductions in the backward slice of ``target_outvars``.

    Walks producer edges from the target output variables; recurses into
    scan/while/cond/pjit sub-jaxprs (positionally mapping outer outvars to
    inner ones), weighting reductions inside a scan by its trip count —
    a per-microbatch psum under gradient accumulation counts accum_steps
    times, which is exactly the DP202 failure mode.
    """
    import jax.extend.core as core

    producer: dict[Any, Any] = {}
    for eqn in jaxpr.eqns:
        for v in eqn.outvars:
            producer[v] = eqn

    sliced_vars: set = set()
    sliced_eqns: list = []
    sliced_eqn_ids: set[int] = set()
    stack = [v for v in target_outvars if not isinstance(v, core.Literal)]
    while stack:
        v = stack.pop()
        if isinstance(v, core.Literal) or v in sliced_vars:
            continue
        sliced_vars.add(v)
        eqn = producer.get(v)
        if eqn is None:
            continue
        if id(eqn) not in sliced_eqn_ids:
            sliced_eqn_ids.add(id(eqn))
            sliced_eqns.append(eqn)
        stack.extend(eqn.invars)

    count = 0
    for eqn in sliced_eqns:
        if eqn.primitive.name in _REDUCTION_PRIMS \
                or _is_quant_wire_reduction(eqn):
            axes = eqn.params.get("axes") or eqn.params.get("axis_name") or ()
            if isinstance(axes, str):
                axes = (axes,)
            if axis in tuple(axes):
                count += 1
            continue
        for sub, mult in _sub_jaxprs(eqn):
            inner_targets = [
                iv for ov, iv in zip(eqn.outvars, sub.jaxpr.outvars)
                if ov in sliced_vars
            ]
            if not inner_targets:
                # Output alignment unknown (or none sliced): be
                # conservative and slice from every inner output.
                inner_targets = list(sub.jaxpr.outvars)
            inner = _count_reductions(sub.jaxpr, inner_targets, axis)
            if inner:
                count += inner * (mult if mult is not None else 2)
    return count


def reduction_report(
    fn: Callable,
    example_args: Sequence[Any],
    axis: str = "data",
    world: int = 8,
) -> dict[str, int]:
    """Per-parameter-leaf data-axis reduction counts for a per-shard step.

    ``fn(state, batch) -> (new_state, metrics)`` is traced on abstract
    values with ``axis`` bound to size ``world``; the report maps the key
    path of every output leaf under a ``params`` subtree to the number of
    data-axis reductions in its backward slice.
    """
    import jax

    closed, out_shape = jax.make_jaxpr(
        fn, axis_env=[(axis, world)], return_shape=True
    )(*example_args)
    out_leaves = jax.tree_util.tree_leaves_with_path(out_shape)
    report: dict[str, int] = {}
    for i, (path, _) in enumerate(out_leaves):
        ks = _keystr(path)
        if not _PARAM_KEY.search(ks):
            continue
        report[ks] = _count_reductions(
            closed.jaxpr, [closed.jaxpr.outvars[i]], axis
        )
    return report


def _fn_location(fn: Callable) -> tuple[str, int]:
    code = getattr(fn, "__code__", None)
    inner = getattr(fn, "__wrapped__", None)
    if code is None and inner is not None:
        code = getattr(inner, "__code__", None)
    if code is None:
        return "<unknown>", 1
    return code.co_filename, code.co_firstlineno


def verify_local_step(
    fn: Callable,
    example_args: Sequence[Any],
    axis: str = "data",
    world: int = 8,
    where: tuple[str, int] | None = None,
    label: str = "local step",
    exact: bool = True,
) -> tuple[list[Finding], dict[str, int]]:
    """Run the gradient-sync contract on one per-shard step function.

    Returns (findings, per-leaf reduction counts). DP201: a parameter leaf
    with zero data-axis reductions. DP202: more than one. DP203: the trace
    bound a collective to an axis the mesh does not define.

    ``exact=False`` relaxes DP202: models with in-forward data-axis
    collectives (sync-BN statistics) put their AD-transpose psums on every
    gradient's backward path, so those programs legitimately carry more
    than one reduction per leaf — only the ≥1 half of the contract (DP201)
    is assertable for them. `verify_repo_step` selects the mode from the
    model's ``axis_name``.
    """
    path, line = where if where is not None else _fn_location(fn)
    try:
        report = reduction_report(fn, example_args, axis=axis, world=world)
    except NameError as e:
        if "unbound axis name" in str(e):
            bad_axis = str(e).rsplit(":", 1)[-1].strip()
            return [Finding(
                "DP203", path, line,
                f"{label}: collective over unknown mesh axis {bad_axis!r} — "
                f"the mesh defines only {axis!r}",
                symbol=label,
            )], {}
        raise
    findings: list[Finding] = []
    for ks, count in sorted(report.items()):
        if count == 0:
            findings.append(Finding(
                "DP201", path, line,
                f"{label}: gradient of {ks} is never reduced over the "
                f"{axis!r} axis — replicas train on local shards and "
                f"silently diverge",
                symbol=label,
            ))
        elif count > 1 and exact:
            findings.append(Finding(
                "DP202", path, line,
                f"{label}: gradient of {ks} is reduced {count}× over the "
                f"{axis!r} axis — repeated averaging silently rescales "
                f"the update",
                symbol=label,
            ))
    return findings, report


def _example_batch(accum_steps: int, batch_size: int):
    import jax.numpy as jnp

    shape_img = (batch_size, 32, 32, 3)
    shape_lbl = (batch_size,)
    if accum_steps > 1:
        shape_img = (accum_steps,) + shape_img
        shape_lbl = (accum_steps,) + shape_lbl
    return {
        "image": jnp.zeros(shape_img, jnp.float32),
        "label": jnp.zeros(shape_lbl, jnp.int32),
    }


def verify_repo_step(
    accum_steps: int = 1,
    model_name: str = "net",
    batch_size: int = 4,
    world: int = 8,
    update_sharding: str = "replicated",
    collective_dtype: str | None = None,
    quant_block_size: int | None = None,
    bucket_mb: float = 0.0,
    **model_kwargs,
) -> tuple[list[Finding], dict[str, int]]:
    """Verify the shipped train step's gradient-sync contract.

    Builds the real model/optimizer/schedule, asks
    `tpu_dp.train.step.make_local_step` for the per-shard program (the one
    `make_train_step(explicit=True)` compiles), and checks every parameter
    leaf's reduction count — under gradient accumulation too, where the
    single reduction must sit after the microbatch scan.

    ``update_sharding="sharded"`` verifies the cross-replica sharded
    weight-update program instead: there the one data-axis reduction per
    leaf is a `reduce_scatter` (counted by `_REDUCTION_PRIMS` exactly like
    psum), followed by a non-reducing params all-gather — so the
    exactly-once invariant holds unchanged across both modes.

    ``collective_dtype="int8"`` verifies the quantized-wire program
    (`train.collective_dtype=int8`): quantizable leaves' reduction is the
    int8-payload `all_to_all` (`_is_quant_wire_reduction`; the f32 scales
    exchange is uncounted metadata), small leaves keep the plain
    `reduce_scatter` — still exactly one data-axis reduction per leaf.
    The traced state carries the per-replica view of the error-feedback
    residuals (`quant.local_residuals`), like the opt-state shards.

    ``bucket_mb > 0`` verifies the bucketed overlap schedule
    (`train.bucket_mb`): each leaf's gradient now reduces inside its
    bucket's concatenated exchange, and the backward slice of each
    parameter output must still contain exactly ONE data-axis reduction —
    a leaf reduced in two buckets (or bucketed AND monolithically) is the
    same DP202 double-averaging bug, just better hidden. The
    `optimization_barrier` token chain that keeps buckets apart deliberately
    couples buckets through their *inputs* only, so it never drags a
    neighbouring bucket's collective onto a foreign leaf's slice.

    Models constructed with ``axis_name`` (sync-BN) perform in-forward
    data-axis collectives whose AD transposes land on the gradient path,
    so for them only the at-least-once half of the contract is asserted
    (``exact=False`` — see `verify_local_step`).
    """
    import jax
    import numpy as np

    from tpu_dp.models import build_model
    from tpu_dp.parallel.dist import DATA_AXIS
    from tpu_dp.train.optim import SGD, shard_optimizer
    from tpu_dp.train.schedule import constant_lr
    from tpu_dp.train.state import create_train_state
    from tpu_dp.train.step import make_local_step

    model = build_model(model_name, **model_kwargs)
    exact = getattr(model, "axis_name", None) is None
    optimizer = SGD(momentum=0.9)
    if update_sharding == "sharded":
        optimizer = shard_optimizer(optimizer, world)
    # Sync-BN models need the data axis bound even at init; an axis-free
    # twin has the identical parameter tree and initializes anywhere.
    init_model = model if exact else build_model(
        model_name,
        **{k: v for k, v in model_kwargs.items() if k != "axis_name"},
    )
    state = create_train_state(
        init_model, jax.random.PRNGKey(0),
        np.zeros((1, 32, 32, 3), np.float32), optimizer,
    )
    if update_sharding == "sharded":
        # The per-shard program sees one replica's slice of the globally
        # sharded optimizer state, not the (world,)-padded global layout.
        state = state.replace(
            opt_state=optimizer.local_view(state.opt_state)
        )
    if collective_dtype in ("int8", "i8"):
        from tpu_dp.parallel import bucketing, quant

        block = quant_block_size or quant.DEFAULT_BLOCK_SIZE
        state = state.replace(residuals=quant.local_residuals(
            quant.init_residuals(
                state.params, world, block,
                bucket_bytes=bucketing.parse_bucket_mb(bucket_mb),
            ), world
        ))
    local_step = make_local_step(
        model, optimizer, constant_lr(0.1),
        accum_steps=accum_steps, world=world, axis_name=DATA_AXIS,
        cast_params=False,  # trace outside a real shard_map scope
        update_sharding=update_sharding,
        collective_dtype=collective_dtype,
        quant_block_size=quant_block_size,
        bucket_mb=bucket_mb,
    )
    wire = f", collective_dtype={collective_dtype!r}" \
        if collective_dtype else ""
    buck = f", bucket_mb={bucket_mb}" if bucket_mb else ""
    return verify_local_step(
        local_step,
        (state, _example_batch(accum_steps, batch_size)),
        axis=DATA_AXIS, world=world,
        label=f"make_local_step(model={model_name!r}, "
              f"accum_steps={accum_steps}, "
              f"update_sharding={update_sharding!r}{wire}{buck})",
        exact=exact,
    )

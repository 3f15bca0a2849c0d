"""Grouped matrix products of the expert layer: a Pallas TPU kernel pair
under a `jax.custom_vjp`, in place of the compiler's `jax.lax.ragged_dot`.

With ``lhs [m, k]``, ``rhs [groups, k, n]`` and ``group_sizes [groups]``
that sum to ``m`` (the rows of group ``g`` follow those of group ``g - 1``),
row ``i`` of the result is ``lhs_i rhs_g`` for the group ``g`` that holds
it: what ``ragged_dot(lhs, rhs, group_sizes)`` computes, with float32
accumulation and the same rounding of the result::

    gmm:   out [m, n]         = lhs [m, k] x rhs_g [k, n]     (a row's own g)
    tgmm:  out [groups, k, n] = sum over g's rows of lhs^T [k, m] x dy [m, n]

`gmm` is the forward product and, over ``rhs`` turned (``rhs_g^T``), the
input's gradient; `tgmm` is the matrices' gradient, written as zeros for a
group with no rows.

**A grid step is a row tile of ``tm`` rows against one group's whole
matrix**, ``k`` and ``n`` whole: a group's matrix is read once for its run
of row tiles, and ``tgmm`` sums a group's tiles in fast memory and writes
its gradient once. ``tm`` is 256, or 128 where 256 does not divide ``m``.
On the v5e at both token models' shapes (the hybrid's ``k, n`` of 2,688 and
1,920, SDAR's of 2,048 and 768) whole ``k`` and ``n`` beat every split
tried, and 256 rows beat 512 and 1,024 in all three products: at 512 the
weight gradient ran at 54% of the peak, at 256 at 82%, at 128 within 2% of
256 (PERF.md §6). Inside a step each kernel loops over slabs of up
to 512 lanes (`_slab`): unrolled, a step's whole product is 1.1–2.9 MiB of
code a kernel, and the hybrid step holds 64 of them (its program's code
grew from 216 to 309 MiB); looped, 0.25–0.5 MiB (229 MiB in all).

**The grid is a static bound**: the ``m / tm`` row tiles plus ``groups - 1``
tiles that a group boundary inside a tile makes twice. Which group and
which row tile a step takes comes from ``group_sizes`` through scalar
prefetch (`_schedule`); steps past the ones the sizes need repeat the last
step's blocks, so they move nothing and compute nothing. A step's time is
therefore its shapes', whatever the sizes are. A tile a group boundary
cuts is computed once for each of its groups, and each writes only its own
rows; a tile inside one group is written whole.

The kernels run where `runs` says: on a TPU or inside `interpret_kernels()`,
for ``m`` of whole 128-row tiles, ``k`` and ``n`` of whole 128 lanes, and
blocks that fit `VMEM_BUDGET`. A row count that is not whole tiles of eight
gave wrong sums through the compiler's kernel on the v5e (PERF.md §7.3);
this pair takes none.

Both kernels carry one operation name, `NAME`, which begins ``ragged-dot``
as the compiler's kernel did: the benchmark's `expert_products_roofline`
reads that prefix. They compile for the TPU; inside
`tpu_dp.ops.interpret_kernels()` the same code runs in the Pallas
interpreter, which is how the tests exercise it on the CPU.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from tpu_dp.ops import _partition
from tpu_dp.ops._partition import interpret as _interpret

F32 = jnp.float32
LANES = 128
NAME = "ragged-dot-gmm"
ROW_TILES = (256, 128)
# What a kernel's blocks may hold, double-buffered, with the product's
# float32 result beside them: most of the v5e's 128 MiB of fast memory.
VMEM_BUDGET = 96 * 2**20
_NT = (((1,), (1,)), ((), ()))      # a b^T
_TN = (((0,), (0,)), ((), ()))      # a^T b


def row_tile(m: int) -> int:
    return next(t for t in ROW_TILES if m % t == 0)


def _footprint(tm, k, n, out_bytes, weights):
    """Bytes of fast memory a kernel's blocks take (bfloat16 operands)."""
    if weights:   # lhs [tm, k] and dy [tm, n] in, [k, n] out and summed
        return 2 * 2 * tm * (k + n) + (2 * out_bytes + 8) * k * n
    # lhs [tm, k] and the group's matrix in, [tm, n] out
    return 2 * 2 * k * (tm + n) + (2 * out_bytes + 4) * tm * n


def fits(m: int, k: int, n: int) -> bool:
    """Whether the kernels take a product of these shapes: ``m`` whole row
    tiles of 128 (so whole tiles of eight), ``k`` and ``n`` whole 128
    lanes, and both kernels' blocks, at float32 results, inside
    `VMEM_BUDGET`."""
    if not (m > 0 and k > 0 and n > 0 and m % ROW_TILES[-1] == 0
            and k % LANES == 0 and n % LANES == 0):
        return False
    tm = row_tile(m)
    return max(_footprint(tm, k, n, 4, False), _footprint(tm, n, k, 4, False),
               _footprint(tm, k, n, 4, True)) <= VMEM_BUDGET


def runs(m: int, k: int, n: int) -> bool:
    """Whether a product goes through the kernels: the shapes fit and the
    kernels can run here (on a TPU, or inside `interpret_kernels()`)."""
    return fits(m, k, n) and _partition.kernels_can_run()


# ----------------------------------------------------------------- schedule

def _schedule(group_sizes, m: int, tm: int, visit_empty: bool):
    """``(group, tile, starts, ends, used)``: for each of the static ``m / tm
    + groups - 1`` grid steps the group and the row tile it takes, the
    groups' first and one-past-last rows, and how many steps the sizes
    need. A group takes each tile its rows touch, in order; a group with no
    rows takes none, or one (its first row's tile) if ``visit_empty``, where
    its result must still be written. Steps from ``used`` on repeat the last
    one."""
    groups = group_sizes.shape[0]
    n_tiles = m // tm
    steps = n_tiles + groups - 1
    sizes = group_sizes.astype(jnp.int32)
    ends = jnp.cumsum(sizes)
    starts = ends - sizes
    first = jnp.minimum(starts // tm, n_tiles - 1)
    last = jnp.where(sizes > 0, (ends - 1) // tm, first)
    count = jnp.where(sizes > 0, last - first + 1, 1 if visit_empty else 0)
    step_end = jnp.cumsum(count)
    used = step_end[-1]
    t = jnp.minimum(jnp.arange(steps, dtype=jnp.int32), used - 1)
    group = jnp.sum(step_end[None, :] <= t[:, None], axis=1, dtype=jnp.int32)
    group = jnp.minimum(group, groups - 1)
    tile = first[group] + t - (step_end - count)[group]
    return group, tile, starts, ends, used[None]


def _rows_of(group, tile, starts, ends, t, tm):
    """``(whole, mask)`` of step ``t``'s row tile: whether every row is its
    group's, and the rows that are ``[tm, 1]``."""
    g = group[t]
    lo, hi = starts[g], ends[g]
    at = tile[t] * tm
    rows = at + jax.lax.broadcasted_iota(jnp.int32, (tm, 1), 0)
    return (lo <= at) & (at + tm <= hi), (rows >= lo) & (rows < hi)


# ------------------------------------------------------------------ kernels

def _slab(width: int) -> int:
    """The lanes of a kernel's inner loop over ``width``: the widest of
    512, 384, 256, 128 that divides it."""
    return next(t for t in (512, 384, 256, LANES) if width % t == 0)


def _gmm_kernel(group, tile, starts, ends, used, lhs_ref, rhs_ref, out_ref,
                *, tm, turned):
    """A row tile of one group against that group's matrix, written where
    the rows are the group's, a slab of the result's lanes at a time."""
    t = pl.program_id(0)
    n = out_ref.shape[1]
    width = _slab(n)
    whole, mask = _rows_of(group, tile, starts, ends, t, tm)

    def slab(j, carry):
        at = pl.ds(pl.multiple_of(j * width, width), width)
        if turned:
            y = jax.lax.dot_general(lhs_ref[...], rhs_ref[at, :], _NT,
                                    preferred_element_type=F32)
        else:
            y = jnp.dot(lhs_ref[...], rhs_ref[:, at],
                        preferred_element_type=F32)

        @pl.when(whole)
        def _():
            out_ref[:, at] = y.astype(out_ref.dtype)

        @pl.when(jnp.logical_not(whole))
        def _():
            out_ref[:, at] = jnp.where(mask, y, out_ref[:, at].astype(F32)
                                       ).astype(out_ref.dtype)
        return carry

    @pl.when(t < used[0])
    def _():
        jax.lax.fori_loop(0, n // width, slab, 0)


def _tgmm_kernel(group, tile, starts, ends, used, lhs_ref, dy_ref, out_ref,
                 acc, *, tm):
    """A group's row tiles follow each other: each adds ``lhs^T dy`` over
    its group's rows to ``acc``, and the group's last writes it; a slab of
    ``k`` at a time, in a loop, as in `_gmm_kernel`."""
    t = pl.program_id(0)
    g = group[t]
    steps = pl.num_programs(0)
    k = acc.shape[0]
    width = _slab(k)
    new = (t == 0) | (group[jnp.maximum(t - 1, 0)] != g)
    ends_here = (t == steps - 1) | (group[jnp.minimum(t + 1, steps - 1)] != g)
    whole, mask = _rows_of(group, tile, starts, ends, t, tm)
    live = (t < used[0]) & (ends[g] > starts[g])

    def slab(i, carry):
        at = pl.ds(pl.multiple_of(i * width, width), width)

        @pl.when(new)
        def _():
            acc[at, :] = jnp.zeros((width, acc.shape[1]), F32)

        @pl.when(live & whole)
        def _():
            acc[at, :] += jax.lax.dot_general(
                lhs_ref[:, at], dy_ref[...], _TN, preferred_element_type=F32)

        @pl.when(live & jnp.logical_not(whole))
        def _():
            lhs = jnp.where(mask, lhs_ref[:, at], 0).astype(lhs_ref.dtype)
            dy = jnp.where(mask, dy_ref[...], 0).astype(dy_ref.dtype)
            acc[at, :] += jax.lax.dot_general(lhs, dy, _TN,
                                              preferred_element_type=F32)

        @pl.when(ends_here)
        def _():
            out_ref[at, :] = acc[at, :].astype(out_ref.dtype)
        return carry

    jax.lax.fori_loop(0, k // width, slab, 0)


# ----------------------------------------------------------------- the calls

def _params(footprint):
    return pltpu.CompilerParams(dimension_semantics=("arbitrary",),
                                vmem_limit_bytes=footprint + 16 * 2**20)


@functools.partial(jax.jit, static_argnames=("out_dtype", "turned",
                                             "interpret"))
def gmm(lhs, rhs, group_sizes, out_dtype, turned=False, interpret=False):
    """``out [m, n]``: row ``i`` of ``lhs [m, k]`` times its group's ``rhs
    [groups, k, n]`` (``[groups, n, k]``, used turned, if ``turned``),
    float32 sums rounded to ``out_dtype``. (Under `jax.jit`, here and on
    `tgmm`, so that the layers of a model trace a kernel's body once
    between them; ``interpret`` is `_interpret()` at the caller's.)"""
    m, k = lhs.shape
    groups, n = rhs.shape[0], rhs.shape[1 if turned else 2]
    out_bytes = jnp.dtype(out_dtype).itemsize
    tm = row_tile(m)
    schedule = _schedule(group_sizes, m, tm, visit_empty=False)
    return pl.pallas_call(
        functools.partial(_gmm_kernel, tm=tm, turned=turned),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=schedule[0].shape,
            in_specs=[pl.BlockSpec((tm, k), lambda t, group, tile, *_:
                                   (tile[t], 0)),
                      pl.BlockSpec((None,) + rhs.shape[1:],
                                   lambda t, group, *_: (group[t], 0, 0))],
            out_specs=pl.BlockSpec((tm, n), lambda t, group, tile, *_:
                                   (tile[t], 0))),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        compiler_params=_params(_footprint(tm, k, n, out_bytes, False)),
        cost_estimate=pl.CostEstimate(
            flops=2 * m * k * n, transcendentals=0,
            bytes_accessed=2 * (m * k + groups * k * n) + m * n * out_bytes),
        interpret=interpret, name=NAME,
    )(*schedule, lhs, rhs)


@functools.partial(jax.jit, static_argnames=("out_dtype", "interpret"))
def tgmm(lhs, dy, group_sizes, out_dtype, interpret=False):
    """``out [groups, k, n]``: ``lhs [m, k]^T dy [m, n]`` over each group's
    rows, float32 sums rounded to ``out_dtype``; zeros for a group with no
    rows."""
    m, k = lhs.shape
    n, groups = dy.shape[1], group_sizes.shape[0]
    out_bytes = jnp.dtype(out_dtype).itemsize
    tm = row_tile(m)
    schedule = _schedule(group_sizes, m, tm, visit_empty=True)

    def by_rows(t, group, tile, *_):
        return tile[t], 0

    return pl.pallas_call(
        functools.partial(_tgmm_kernel, tm=tm),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=schedule[0].shape,
            in_specs=[pl.BlockSpec((tm, k), by_rows),
                      pl.BlockSpec((tm, n), by_rows)],
            out_specs=pl.BlockSpec((None, k, n), lambda t, group, *_:
                                   (group[t], 0, 0)),
            scratch_shapes=[pltpu.VMEM((k, n), F32)]),
        out_shape=jax.ShapeDtypeStruct((groups, k, n), out_dtype),
        compiler_params=_params(_footprint(tm, k, n, out_bytes, True)),
        cost_estimate=pl.CostEstimate(
            flops=2 * m * k * n, transcendentals=0,
            bytes_accessed=2 * m * (k + n) + groups * k * n * out_bytes),
        interpret=interpret, name=NAME,
    )(*schedule, lhs, dy)


# ----------------------------------------------------------------- gradient

@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _product(lhs, rhs, group_sizes, out_dtype):
    return gmm(lhs, rhs, group_sizes, out_dtype, interpret=_interpret())


def _product_fwd(lhs, rhs, group_sizes, out_dtype):
    return _product(lhs, rhs, group_sizes, out_dtype), (lhs, rhs,
                                                         group_sizes)


def _product_bwd(out_dtype, residuals, dy):
    lhs, rhs, group_sizes = residuals
    # The cotangent in the operands' dtype, as the compiler's default
    # precision rounds a float32 one for its products.
    dy = dy.astype(lhs.dtype)
    interpret = _interpret()
    dlhs = gmm(dy, rhs, group_sizes, lhs.dtype, turned=True,
               interpret=interpret)
    drhs = tgmm(lhs, dy, group_sizes, rhs.dtype, interpret=interpret)
    return dlhs, drhs, None


_product.defvjp(_product_fwd, _product_bwd)


def ragged_dot(lhs, rhs, group_sizes, preferred_element_type=None):
    """``jax.lax.ragged_dot(lhs, rhs, group_sizes, preferred_element_type=
    ...)`` of ``lhs [m, k]`` and ``rhs [groups, k, n]``, with ``group_sizes``
    that sum to ``m``: through the kernel pair where `runs` and the two are
    of one dtype, through the compiler's kernel elsewhere (chosen at trace
    time). Differentiable in ``lhs`` and ``rhs``."""
    m, k = lhs.shape
    n = rhs.shape[2]
    if lhs.dtype != rhs.dtype or not runs(m, k, n):
        return jax.lax.ragged_dot(
            lhs, rhs, group_sizes,
            preferred_element_type=preferred_element_type)
    out_dtype = jnp.dtype(preferred_element_type or lhs.dtype)
    return _product(lhs, rhs, group_sizes.astype(jnp.int32), out_dtype)

"""The grouped-product kernel pair (`tpu_dp/ops/grouped_products.py`), in
the Pallas interpreter, against the compiler's `jax.lax.ragged_dot` and a
dense product of each row with its own group's matrix: the forward, the
input's gradient and the matrices' gradient, float32 at the highest
precision, tiny shapes of whole 128 lanes."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpu_dp.models import parts
from tpu_dp.ops import _partition
from tpu_dp.ops import grouped_products as gp

F32 = jnp.float32


def dense(lhs, rhs, group_sizes, preferred_element_type=None):
    """Row ``i`` times the matrix of the group that holds it."""
    group = jnp.repeat(jnp.arange(rhs.shape[0]), group_sizes,
                       total_repeat_length=lhs.shape[0])
    out = jnp.einsum("mk,mkn->mn", lhs, rhs[group],
                     preferred_element_type=F32)
    return out.astype(preferred_element_type or lhs.dtype)


PRODUCTS = {"kernels": gp.ragged_dot, "compiler": jax.lax.ragged_dot,
            "dense": dense}


def operands(m, k, n, groups, seed=0, zeros_from=None):
    keys = jax.random.split(jax.random.PRNGKey(seed), 2 + groups)
    lhs = jax.random.normal(keys[0], (m, k))
    if zeros_from is not None:
        lhs = lhs.at[zeros_from:].set(0.0)
    return lhs, keys[1:]


# (expert function or None for one product, m, k, n, group sizes, the row
# from which lhs is zeros)
CASES = {
    # boundaries at rows 100, 400 and 437: inside tiles of 256
    "boundaries_inside_tiles": (None, 512, 256, 128, (100, 300, 37, 75),
                                None),
    # group 1 has no rows: it is skipped forward, its gradient is zeros
    "an_empty_group": (None, 512, 128, 256, (256, 0, 200, 56), None),
    # as `experts_share` hands a chunk over: the rows past the held pairs
    # are zeros and belong to the last group
    "the_last_group_takes_the_zero_rows": (None, 512, 128, 128,
                                           (60, 70, 50, 332), 180),
    "gated_silu": (parts.gated_silu, 256, 128, 128, (30, 0, 90, 136), 150),
    "squared_relu": (parts.squared_relu, 256, 128, 256, (7, 120, 1, 128),
                     200),
}


@pytest.mark.parametrize("case", list(CASES))
def test_the_pair_is_ragged_dot(monkeypatch, case):
    """Forward and gradients, through the kernels, the compiler's kernel and
    the dense product, agree within 1e-5 of the largest entry; the kernels
    run (a `pallas_call` in the traced program) and an empty group's
    matrices get a gradient of zeros."""
    expert_fn, m, k, n, sizes, zeros_from = CASES[case]
    groups = len(sizes)
    group_sizes = jnp.asarray(sizes, jnp.int32)
    lhs, keys = operands(m, k, n, groups, zeros_from=zeros_from)
    if expert_fn is None:
        mats = (jax.random.normal(keys[0], (groups, k, n)),)
    else:   # kernels [held, hidden, width] in, one [held, width, hidden] out
        ins = 3 if expert_fn is parts.gated_silu else 2
        mats = tuple(0.3 * jax.random.normal(keys[i], (groups, k, n))
                     for i in range(ins - 1))
        mats += (0.3 * jax.random.normal(keys[ins], (groups, n, k)),)
    out_width = n if expert_fn is None else k
    dy = jax.random.normal(jax.random.PRNGKey(9), (m, out_width))

    def run(product):
        monkeypatch.setattr(parts, "_grouped", product)

        def f(lhs, mats):
            if expert_fn is None:
                return product(lhs, mats[0], group_sizes)
            return expert_fn(lhs, mats, group_sizes)

        def loss(lhs, mats):
            return jnp.sum(f(lhs, mats) * dy)

        with jax.default_matmul_precision("highest"):
            jaxpr = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1)))(lhs, mats)
            return f(lhs, mats), jax.grad(loss, argnums=(0, 1))(lhs, mats), \
                str(jaxpr)

    got = {name: run(product) for name, product in PRODUCTS.items()}
    assert "pallas_call" in got["kernels"][2]
    assert "pallas_call" not in got["compiler"][2]
    for name in ("compiler", "dense"):
        for mine, want in zip(jax.tree_util.tree_leaves(got["kernels"][:2]),
                              jax.tree_util.tree_leaves(got[name][:2])):
            scale = float(jnp.max(jnp.abs(want))) or 1.0
            np.testing.assert_allclose(mine, want, rtol=0, atol=1e-5 * scale)
    for g, size in enumerate(sizes):
        if size == 0:
            for d_mat in got["kernels"][1][1]:
                assert not np.any(np.asarray(d_mat[g]))


@pytest.mark.parametrize("shape, takes", [
    ((256, 128, 128), True), ((384, 256, 1920), True),
    ((200, 128, 128), False), ((264, 128, 128), False),
    ((256, 64, 128), False), ((256, 128, 96), False),
], ids=["tiny", "odd_tiles", "rows_not_whole_tiles",
        "rows_of_whole_eights_only", "k_not_whole_lanes",
        "n_not_whole_lanes"])
def test_the_kernels_take_whole_row_tiles_and_lanes(shape, takes):
    """`runs`: ``m`` whole tiles of 128 rows (a chunk of whole ``tm``), ``k``
    and ``n`` whole 128 lanes; where it says no, `ragged_dot` is the
    compiler's and still right."""
    m, k, n = shape
    assert gp.runs(m, k, n) is takes
    if takes:
        assert m % gp.row_tile(m) == 0
    lhs = jax.random.normal(jax.random.PRNGKey(0), (m, k))
    rhs = jax.random.normal(jax.random.PRNGKey(1), (2, k, n))
    sizes = jnp.asarray([m // 3, m - m // 3], jnp.int32)
    with jax.default_matmul_precision("highest"):
        jaxpr = str(jax.make_jaxpr(gp.ragged_dot)(lhs, rhs, sizes))
        np.testing.assert_allclose(gp.ragged_dot(lhs, rhs, sizes),
                                   dense(lhs, rhs, sizes), rtol=1e-5,
                                   atol=1e-4)
    assert ("pallas_call" in jaxpr) is takes


def test_both_cells_products_fit_and_wider_blocks_are_refused():
    """The hybrid's chunk (24,576 rows, 2,688 and 1,920 wide) and SDAR's
    (32,768 rows, 2,048 and 768) fit in both directions, at 256-row tiles;
    matrices of 4,096 x 4,096 do not fit the kernels' fast memory whole and
    go to the compiler's kernel."""
    for m, k, n in ((24576, 2688, 1920), (32768, 2048, 768)):
        assert gp.fits(m, k, n) and gp.fits(m, n, k)
        assert gp.row_tile(m) == 256
    assert not gp.fits(256, 4096, 4096)


def test_a_cpu_outside_interpret_kernels_takes_the_compilers(monkeypatch):
    monkeypatch.setattr(_partition, "_interpret_requests", 0)
    assert not gp.runs(256, 128, 128)
    lhs, rhs = jnp.ones((256, 128)), jnp.ones((2, 128, 128))
    jaxpr = str(jax.make_jaxpr(gp.ragged_dot)(
        lhs, rhs, jnp.asarray([100, 156], jnp.int32)))
    assert "pallas_call" not in jaxpr and "ragged_dot" in jaxpr


@pytest.mark.parametrize("sizes, visit_empty", [
    ((100, 300, 37, 75), False), ((256, 0, 200, 56), False),
    ((256, 0, 200, 56), True), ((0, 0, 0, 512), True),
    ((512, 0, 0, 0), True), ((1, 1, 1, 509), False),
], ids=["inside", "empty", "empty_visited", "all_last", "all_first",
        "small_first"])
def test_the_schedule_stays_inside_its_static_bound(sizes, visit_empty):
    """Every row tile a group's rows touch is a step of that group, in
    order; the steps the sizes need fit ``m / tm + groups - 1``, and the
    ones past them repeat the last."""
    m, tm = 512, 128
    group, tile, starts, ends, used = map(np.asarray, gp._schedule(
        jnp.asarray(sizes, jnp.int32), m, tm, visit_empty))
    assert len(group) == m // tm + len(sizes) - 1 >= used[0]
    want = []
    for g, size in enumerate(sizes):
        lo = sum(sizes[:g])
        if size:
            last = (lo + size - 1) // tm
            want += [(g, t) for t in range(lo // tm, last + 1)]
        elif visit_empty:
            want.append((g, min(lo // tm, m // tm - 1)))
    assert list(zip(group[:used[0]], tile[:used[0]])) == want
    assert all(x == want[-1] for x in zip(group[used[0]:], tile[used[0]:]))
    assert list(ends - starts) == list(sizes)


def test_the_pair_carries_the_name_the_metric_reads():
    assert gp.NAME.startswith("ragged-dot")

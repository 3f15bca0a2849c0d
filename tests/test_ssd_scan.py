"""The state-space scan's kernel pair (`tpu_dp/ops/ssd_scan.py`) in the
Pallas interpreter: against the chunked form the compiler gets elsewhere
(`nemotron_h.ssd_chunked`) and against the recurrence itself a position at a
time (`benchmark/reference_nemotron_h.py`, loaded by its path), forward and
every gradient; and the predicate that decides, at trace time, which of the
two `ssm_mixer` takes.
"""

from __future__ import annotations

import importlib.util
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpu_dp.models import nemotron_h
from tpu_dp.models.nemotron_h import NemotronH, ssd_chunked, ssm_mixer
from tpu_dp.ops import _partition, ssd_scan

REPO = Path(__file__).resolve().parents[1]
NAMES = ("x", "delta", "a_head", "b", "c", "d_skip")


def load_reference():
    spec = importlib.util.spec_from_file_location(
        "reference_nemotron_h",
        REPO / "benchmark" / "reference_nemotron_h.py")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


ref = load_reference()

# rows, positions (chunks of 128), heads, head_dim, groups, state
CASES = {
    "one_row_one_group": (1, 384, 8, 16, 1, 128),
    "two_rows_two_groups": (2, 384, 16, 64, 2, 128),
    "whole_lane_heads_wide_state": (1, 512, 8, 128, 1, 256),
}


def operands(case, dtype=jnp.float32, seed=4):
    """Decays over four decades (``delta * a`` from -1e-3 to -10), a step in
    eight with ``delta = 0``, a state carried over every chunk seam."""
    rows, length, heads, p, groups, n = CASES[case]
    keys = jax.random.split(jax.random.PRNGKey(seed), 7)
    x = jax.random.normal(keys[0], (rows, length, heads * p)).astype(dtype)
    b = jax.random.normal(keys[1], (rows, length, groups * n)).astype(dtype)
    c = jax.random.normal(keys[2], (rows, length, groups * n)).astype(dtype)
    delta = jnp.exp(jax.random.uniform(keys[3], (rows, length, heads),
                                       minval=np.log(1e-3), maxval=0.0))
    delta = delta * (jax.random.uniform(keys[4], delta.shape) > 0.125)
    a = -jnp.exp(jax.random.uniform(keys[5], (heads,), minval=0.0,
                                    maxval=np.log(10.0)))
    d = jax.random.normal(keys[6], (heads,))
    return (x, delta, a, b, c, d), groups


def by_kernels(x, delta, a, b, c, d, groups):
    """The pair's result, which is a group at a time, as the oracles'."""
    y = ssd_scan.ssd_scan(x, delta, a, b, c, d, groups)
    assert y.shape == (x.shape[0], groups, x.shape[1], x.shape[2] // groups)
    return jnp.swapaxes(y, 1, 2).reshape(x.shape)


def _heads(x, delta, b, c, groups):
    rows, length, heads = delta.shape
    return (x.reshape(rows, length, heads, -1),
            b.reshape(rows, length, groups, -1),
            c.reshape(rows, length, groups, -1))


def by_chunks(x, delta, a, b, c, d, groups):
    x4, b4, c4 = _heads(x, delta, b, c, groups)
    y = ssd_chunked(x4, delta, a, b4, c4, ssd_scan.CHUNK)
    return (y + d[:, None] * x4.astype(jnp.float32)).reshape(x.shape)


def by_recurrence(x, delta, a, b, c, d, groups):
    """Float32 whatever the operands' dtype, a row at a time."""
    f32 = jnp.float32
    x4, b4, c4 = _heads(x.astype(f32), delta, b.astype(f32), c.astype(f32),
                        groups)
    r = delta.shape[-1] // groups
    rows = [ref.recurrence(x4[i], delta[i], a, jnp.repeat(b4[i], r, axis=1),
                           jnp.repeat(c4[i], r, axis=1))
            for i in range(x.shape[0])]
    return (jnp.stack(rows) + d[:, None] * x4).reshape(x.shape)


def gradients(fn, args, groups):
    """Of a weighted sum of the outputs (a plain sum would hand every
    position the same cotangent), in all six operands."""
    weight = jax.random.normal(jax.random.PRNGKey(11), args[0].shape)
    return jax.grad(lambda *t: jnp.sum(fn(*t, groups) * weight),
                    argnums=tuple(range(6)))(*args)


def gap(got, want):
    got, want = (np.asarray(t, np.float32) for t in (got, want))
    return float(np.max(np.abs(got - want)) / (np.max(np.abs(want)) + 1e-30))


@pytest.mark.parametrize("oracle", [by_chunks, by_recurrence])
@pytest.mark.parametrize("case", CASES)
def test_forward_in_float32(case, oracle):
    """The scan test's tolerance (rtol 1e-4, atol 1e-5): the same sums in
    another order."""
    args, groups = operands(case)
    with jax.default_matmul_precision("highest"):
        got, want = by_kernels(*args, groups), oracle(*args, groups)
    assert got.dtype == jnp.float32 and got.shape == args[0].shape
    np.testing.assert_allclose(got, want, rtol=1e-4,
                               atol=1e-5 * float(jnp.max(jnp.abs(want))))


@pytest.mark.parametrize("oracle", [by_chunks, by_recurrence])
@pytest.mark.parametrize("case", CASES)
def test_every_gradient_in_float32(case, oracle):
    """``x``, ``delta``, ``a``, ``B``, ``C`` and ``D`` (the skip rides in
    the kernels): each within 1e-4 of the oracle's largest entry."""
    args, groups = operands(case)
    with jax.default_matmul_precision("highest"):
        got = gradients(by_kernels, args, groups)
        want = gradients(oracle, args, groups)
    for name, g, w in zip(NAMES, got, want):
        assert g.shape == w.shape and g.dtype == w.dtype, name
        assert gap(g, w) < 1e-4, (name, gap(g, w))


@pytest.mark.parametrize("case", CASES)
def test_bfloat16_stays_inside_the_models_band(case):
    """The operands in bfloat16 against the float32 recurrence on the same
    (rounded) operands: the output within 0.01 and every gradient within
    0.12 of the oracle's largest entry, the band the model's
    `test_bfloat16_stays_inside_a_band_that_float8_leaves` holds the whole
    program to; and no further from it than the chunked form is, by more
    than a rounding."""
    args, groups = operands(case, jnp.bfloat16)
    want_y = by_recurrence(*args, groups)
    want = gradients(by_recurrence, args, groups)
    got_y, plain_y = by_kernels(*args, groups), by_chunks(*args, groups)
    assert got_y.dtype == jnp.float32
    assert gap(got_y, want_y) < 0.01
    assert gap(got_y, want_y) < gap(plain_y, want_y) + 2e-3
    got = gradients(by_kernels, args, groups)
    for name, g, w, arg in zip(NAMES, got, want, args):
        assert g.dtype == arg.dtype, name
        assert gap(g, w) < 0.12, (name, gap(g, w))


def test_steps_of_delta_zero_neither_decay_nor_add():
    """A row whose second chunk is all ``delta = 0``: the state leaves it as
    it entered, and the third chunk reads what the first one left."""
    (x, delta, a, b, c, d), groups = operands("one_row_one_group")
    delta = delta.at[:, 128:256].set(0.0)
    got = by_kernels(x, delta, a, b, c, d, groups)
    cut = tuple(jnp.concatenate([t[:, :128], t[:, 256:]], axis=1)
                for t in (x, delta, b, c))
    want = by_kernels(cut[0], cut[1], a, cut[2], cut[3], d, groups)
    np.testing.assert_allclose(got[:, 256:], want[:, 128:], rtol=1e-5,
                               atol=1e-5)
    # inside the still chunk only the state it was handed and the skip speak
    assert float(jnp.max(jnp.abs(got[:, 128:256]))) > 0.0


# ------------------------------------------------------------ the predicate

FITS = dict(length=256, chunk=128, heads=8, head_dim=16, groups=1, state=128)


@pytest.mark.parametrize("change,why", [
    ({}, None),
    ({"heads": 64, "head_dim": 64, "groups": 8, "length": 8192}, None),
    ({"length": 200}, "a row that is not whole chunks"),
    ({"chunk": 64}, "another chunk than the published one"),
    ({"state": 64}, "a state that is not whole 128 lanes"),
    ({"head_dim": 8}, "a group's heads that are not whole 128 lanes"),
    ({"heads": 8, "groups": 2, "head_dim": 32},
     "a group's heads that are not whole sublane tiles"),
    ({"head_dim": 96, "heads": 4}, "heads that cut a 128-lane piece"),
], ids=["tiny", "published", "ragged_row", "chunk", "state", "lanes",
        "sublanes", "odd_heads"])
def test_the_shapes_the_kernels_take(change, why):
    assert ssd_scan.fits(**{**FITS, **change}) == (why is None), why
    assert ssd_scan.runs(**{**FITS, **change}) == (why is None)


def kernel_names(jaxpr):
    """The names of the `pallas_call`s of a jaxpr, nested ones included."""
    names = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            names.append(" ".join(str(v) for k, v in eqn.params.items()
                                  if "name" in k))
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) else [value]:
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    names += kernel_names(sub)
    return names


def mixer_kernels(monkeypatch, length=256, state=128, interpret=True):
    """The kernels of `ssm_mixer`, forward and backward, as it is traced
    for a tiny model of these shapes."""
    if not interpret:
        monkeypatch.setattr(_partition, "_interpret_requests", 0)
    model = NemotronH(num_classes=64, hidden_size=64, layer_pattern="M",
                      ssm_heads=8, ssm_head_dim=16, ssm_state=state,
                      ssm_groups=1)
    params = model.init(jax.random.PRNGKey(0))["params"]["layers_0"]["mixer"]
    u = jax.random.normal(jax.random.PRNGKey(1), (2, length, 64))
    traced = jax.make_jaxpr(jax.grad(
        lambda p, u: jnp.sum(ssm_mixer(p, u, model))))(params, u)
    return kernel_names(traced.jaxpr)


def test_the_mixer_takes_the_pair_where_the_predicate_says_so(monkeypatch):
    names = mixer_kernels(monkeypatch)
    assert len(names) == 2 and all(
        n.startswith(ssd_scan.NAME) for n in names), names
    assert ssd_scan.NAME.startswith("ssd_scan")   # the metric's prefix


@pytest.mark.parametrize("refused", [
    {"length": 200}, {"state": 64}, {"interpret": False}],
    ids=["ragged_row", "narrow_state", "a_cpu_outside_interpret_kernels"])
def test_the_mixer_takes_the_chunked_form_elsewhere(monkeypatch, refused):
    assert mixer_kernels(monkeypatch, **refused) == []


def test_the_mixer_is_the_same_function_either_way(monkeypatch):
    """One tiny state-space layer through the pair and, with the predicate
    answering no, through `ssd_chunked`: output and every leaf's gradient
    within 2e-5 of the largest entry."""
    model = NemotronH(num_classes=64, hidden_size=64, layer_pattern="M",
                      ssm_heads=8, ssm_head_dim=16, ssm_state=128,
                      ssm_groups=1)
    params = model.init(jax.random.PRNGKey(0))["params"]["layers_0"]["mixer"]
    u = jax.random.normal(jax.random.PRNGKey(1), (2, 256, 64))

    def run():
        return jax.value_and_grad(
            lambda p: jnp.sum(jnp.square(ssm_mixer(p, u, model))))(params)

    with jax.default_matmul_precision("highest"):
        got = run()
        monkeypatch.setattr(nemotron_h.ssd_scan, "runs",
                            lambda *shape: False)
        want = run()
    assert float(got[0]) == pytest.approx(float(want[0]), rel=1e-5)
    gaps = jax.tree_util.tree_map(gap, got[1], want[1])
    assert max(jax.tree_util.tree_leaves(gaps)) < 2e-5, gaps

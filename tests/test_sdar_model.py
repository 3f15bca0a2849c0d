"""The block-diffusion mixture-of-experts decoder against its plain reference.

`tpu_dp/models/sdar.py` (chunked attention under the block-diffusion mask,
grouped products over a share of the experts, head and loss by the row)
against `benchmark/reference_sdar.py` (dense attention with the mask written
out, the experts by a plain loop), which is loaded by its path and imports
nothing of the program. Seeded weights, tiny widths: hidden 64, 4 heads / 2
kv heads of 16, 8 experts top-2 of width 32, vocabulary 64, rows of 32
tokens in blocks of 4.
"""

from __future__ import annotations

import importlib.util
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpu_dp.models import build_model
from tpu_dp.models.sdar import (
    COUNTER_NAMES,
    KERNEL_TILE,
    block_diffusion_attention,
    experts_share,
    flash_block_diffusion_attention,
    kernel_fits,
    route,
)

REPO = Path(__file__).resolve().parents[1]


def load_reference():
    spec = importlib.util.spec_from_file_location(
        "reference_sdar", REPO / "benchmark" / "reference_sdar.py")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


ref = load_reference()

LENGTH, BLOCK, ROWS, VOCAB = 32, 4, 3, 64
# The reference's view of the tiny model: the published config's keys.
MODEL = dict(
    hidden_size=64, num_hidden_layers=2, num_attention_heads=4,
    num_key_value_heads=2, head_dim=16, moe_intermediate_size=32,
    router_experts=8, num_experts=4, num_experts_per_tok=2,
    norm_topk_prob=True, rope_theta=1e6, rms_norm_eps=1e-6, vocab_size=VOCAB,
    block_length=BLOCK, mask_token_id=VOCAB - 1, share_index=0, init_std=0.02)
# The program's view of the same shapes (`ModelConfig`'s keys).
SHAPES = dict(
    hidden_size=64, num_layers=2, num_heads=4, num_kv_heads=2, head_dim=16,
    expert_width=32, num_experts=8, experts_per_token=2, experts_held=4,
    share_index=0, block_length=BLOCK, rope_theta=1e6)


def tiny_model(dtype=jnp.float32, **overrides):
    # several chunks of queries and of pairs, so every seam is crossed
    model = build_model("sdar_moe", num_classes=VOCAB, dtype=dtype,
                        **{**SHAPES, **overrides})
    return model.__class__(**{**model.__dict__, "attn_chunk": 8,
                              "moe_chunks": 4})


def weights(seed=7, scale=5.0):
    """Seeded weights, the matrices scaled up so that routing, attention and
    the loss are far from their uniform starts."""
    params = ref.init_params(MODEL, seed)
    return jax.tree_util.tree_map(
        lambda x: x * scale if x.ndim >= 2 else x, params)


def rows_and_noise(step=0):
    x0 = jax.random.randint(jax.random.PRNGKey(1), (ROWS, LENGTH), 0,
                            VOCAB - 1)
    return x0, ref.draw_noise(1, step, x0, BLOCK, VOCAB - 1)


def program_loss_and_grads(model, params, inputs):
    def loss_fn(p):
        out = model.apply({"params": p}, inputs, train=True)
        return jnp.mean(out.loss), out

    return jax.jit(jax.value_and_grad(loss_fn, has_aux=True))(params)


def worst_gap(tree, want):
    gaps = jax.tree_util.tree_map(
        lambda a, b: float(jnp.max(jnp.abs(a - b))
                           / (jnp.max(jnp.abs(b)) + 1e-30)), tree, want)
    return max(jax.tree_util.tree_leaves(gaps))


# ------------------------------------------------- (a) forward, loss, grads

def test_the_programs_tree_is_the_references():
    model = tiny_model()
    ours = model.init(jax.random.PRNGKey(0))["params"]
    theirs = ref.init_params(MODEL, 3)
    shapes = lambda t: jax.tree_util.tree_map(lambda x: (x.shape, x.dtype), t)  # noqa: E731
    assert shapes(ours) == shapes(theirs)
    # normal(0, 0.02) matrices, norm weights 1
    assert float(jnp.std(ours["head"]["kernel"])) == pytest.approx(0.02,
                                                                  rel=0.1)
    assert np.all(np.asarray(ours["final_norm"]["scale"]) == 1.0)


def test_the_noise_is_the_references_draw():
    model = tiny_model()
    x0, (xt, weight) = rows_and_noise(step=5)
    got = model.make_noise_fn(1)(5, x0)
    assert np.array_equal(got["xt"], xt) and np.array_equal(got["x0"], x0)
    np.testing.assert_allclose(got["weight"], weight, rtol=1e-6)
    masked = np.asarray(weight) > 0
    assert np.all(np.asarray(xt)[masked] == VOCAB - 1)
    assert 0 < masked.sum() < masked.size


def test_forward_loss_and_gradients_match_the_reference_in_float32():
    model, params = tiny_model(), weights()
    x0, (xt, weight) = rows_and_noise()
    (loss, out), grads = program_loss_and_grads(
        model, params, {"xt": xt, "x0": x0, "weight": weight})
    want_loss, want_grads, want_hits = ref.loss_and_grads(
        params, x0, (xt, weight), MODEL)
    assert float(loss) == pytest.approx(float(want_loss), rel=1e-5)
    assert int(jnp.sum(out.correct)) == int(want_hits)
    assert int(jnp.sum(out.count)) == int(np.sum(np.asarray(weight) > 0))
    assert worst_gap(grads, want_grads) < 1e-5


def test_bfloat16_stays_inside_a_band_that_float8_leaves():
    """The program computing in bfloat16 lies within 2% of the float32
    reference's loss and 12% of its gradient, leaf by leaf in norm; the
    reference itself computed in float8 leaves both bands."""
    params = weights()
    x0, (xt, weight) = rows_and_noise()
    inputs = {"xt": xt, "x0": x0, "weight": weight}
    want_loss, want_grads, _ = ref.loss_and_grads(params, x0, (xt, weight),
                                                  MODEL)

    def gaps(loss, grads):
        norms = jax.tree_util.tree_map(
            lambda g, w: float(abs(jnp.linalg.norm(g) - jnp.linalg.norm(w))
                               / jnp.linalg.norm(w)), grads, want_grads)
        return (abs(float(loss) - float(want_loss)) / float(want_loss),
                max(jax.tree_util.tree_leaves(norms)))

    (loss, _), grads = program_loss_and_grads(
        tiny_model(jnp.bfloat16), params, inputs)
    loss_gap, grad_gap = gaps(loss, grads)
    assert loss_gap < 0.02 and grad_gap < 0.12, (loss_gap, grad_gap)
    f8_loss, f8_grads, _ = ref.loss_and_grads(
        params, x0, (xt, weight), MODEL, dtype=jnp.float8_e4m3fn)
    f8_loss_gap, f8_grad_gap = gaps(f8_loss, f8_grads)
    assert f8_loss_gap > 0.02 or f8_grad_gap > 0.12, (f8_loss_gap,
                                                      f8_grad_gap)


def test_a_step_counts_what_it_did():
    model, params = tiny_model(), weights()
    x0, (xt, weight) = rows_and_noise()
    (_, out), _ = program_loss_and_grads(
        model, params, {"xt": xt, "x0": x0, "weight": weight})
    counts = dict(zip(COUNTER_NAMES, np.asarray(out.counters)))
    positions, layers = ROWS * 2 * LENGTH, MODEL["num_hidden_layers"]
    assert counts["moe.assignments"] == positions * 2 * layers
    assert 0 < counts["moe.assignments_held"] < counts["moe.assignments"]
    assert counts["moe.assignments_dropped"] == 0
    assert counts["moe.load_mean_sum"] == counts["moe.assignments_held"] / 4
    assert counts["moe.load_max_sum"] >= counts["moe.load_mean_sum"]
    assert counts["diffusion.tokens"] == ROWS * LENGTH
    assert counts["diffusion.masked_tokens"] == np.sum(np.asarray(weight) > 0)


# --------------------------------------------------------------- (b) the mask

def rule(i: int, j: int) -> bool:
    """May query ``i`` see key ``j``, over ``[noisy ; clean]``? The three
    rules, written out."""
    q_noisy, k_noisy = i < LENGTH, j < LENGTH
    q_blk, k_blk = (i % LENGTH) // BLOCK, (j % LENGTH) // BLOCK
    if q_noisy and k_noisy:
        return k_blk == q_blk          # the noisy keys of its own block
    if q_noisy and not k_noisy:
        return k_blk < q_blk           # the clean keys of earlier blocks
    if not q_noisy and k_noisy:
        return False                   # a clean query sees no noisy key
    return k_blk <= q_blk              # the clean keys up to its own block


QUADRANTS = {"noisy_noisy": (0, 0), "noisy_clean": (0, 1),
             "clean_noisy": (1, 0), "clean_clean": (1, 1)}


@pytest.fixture(scope="module")
def seen_by_the_program():
    """Which keys each query's output is made of: with all scores equal and
    the value of key ``j`` the ``j``-th unit vector, query ``i``'s output
    is positive at ``j`` exactly where it sees ``j``."""
    n = 2 * LENGTH
    q = jnp.zeros((1, n, 1, n), jnp.float32)
    v = jnp.eye(n, dtype=jnp.float32).reshape(1, n, 1, n)
    out = block_diffusion_attention(q, q, v, BLOCK, chunk=8)
    out = np.asarray(out).reshape(n, n)
    np.testing.assert_allclose(out.sum(-1), 1.0, rtol=1e-6)
    return out > 0


@pytest.mark.parametrize("quadrant", QUADRANTS)
def test_every_quadrant_of_the_mask_follows_the_rules(quadrant,
                                                      seen_by_the_program):
    qh, kh = QUADRANTS[quadrant]
    rows = slice(qh * LENGTH, (qh + 1) * LENGTH)
    cols = slice(kh * LENGTH, (kh + 1) * LENGTH)
    want = np.array([[rule(i, j) for j in range(2 * LENGTH)]
                     for i in range(2 * LENGTH)])
    assert np.array_equal(seen_by_the_program[rows, cols], want[rows, cols])
    assert np.array_equal(
        np.asarray(ref.attention_mask(LENGTH, BLOCK))[rows, cols],
        want[rows, cols])
    if quadrant == "clean_noisy":
        assert not seen_by_the_program[rows, cols].any()


def test_a_row_that_is_not_whole_chunks_is_refused():
    q = jnp.zeros((1, 2 * 12, 1, 8))
    with pytest.raises(ValueError, match="multiple of the attention chunk"):
        block_diffusion_attention(q, q, q, BLOCK, chunk=8)


def test_the_flash_kernel_is_the_compilers_form():
    """The shipped flash kernel with the mask computed from the indices
    (interpreted here) against the chunked products, forward and backward,
    at the least shapes it takes: heads of 128, a tile a half row."""
    rows, length, heads, kv, d = 2, KERNEL_TILE, 4, 2, 128
    assert kernel_fits(length, d) and not kernel_fits(LENGTH, 16)
    keys = jax.random.split(jax.random.PRNGKey(0), 4)
    q = jax.random.normal(keys[0], (rows, 2 * length, heads, d))
    k = jax.random.normal(keys[1], (rows, 2 * length, kv, d))
    v = jax.random.normal(keys[2], (rows, 2 * length, kv, d))
    weigh = jax.random.normal(keys[3], q.shape)

    def plain(q, k, v):
        return block_diffusion_attention(q, k, v, BLOCK, chunk=256)

    def flash(q, k, v):
        return flash_block_diffusion_attention(q, k, v, BLOCK, interpret=True)

    np.testing.assert_allclose(flash(q, k, v), plain(q, k, v), atol=2e-5)
    grads = [jax.grad(lambda *a, f=f: jnp.sum(f(*a) * weigh), (0, 1, 2))(
        q, k, v) for f in (flash, plain)]
    for got, want in zip(*grads):
        np.testing.assert_allclose(got, want, atol=5e-5)


def test_the_model_takes_the_kernel_where_its_shapes_fit(monkeypatch):
    """Inside `interpret_kernels()` (every test) a model with heads of 128
    on rows of whole tiles runs the kernel, and gives what the chunked
    products give (the model as it is where the kernel does not fit)."""
    import tpu_dp.models.sdar as sdar

    model = build_model(
        "sdar_moe", num_classes=VOCAB, hidden_size=32, num_layers=1,
        num_heads=2, num_kv_heads=1, head_dim=128, expert_width=16,
        num_experts=4, experts_per_token=2, experts_held=2)
    params = jax.tree_util.tree_map(
        lambda x: x * 5 if x.ndim >= 2 else x,
        model.init(jax.random.PRNGKey(2))["params"])
    x0 = jax.random.randint(jax.random.PRNGKey(3), (1, KERNEL_TILE), 0,
                            VOCAB - 1)
    inputs = model.make_noise_fn(1)(0, x0)
    calls, real = [], sdar.flash_attention_by_head

    def spy(*args, **kwargs):
        calls.append(1)
        return real(*args, **kwargs)

    monkeypatch.setattr(sdar, "flash_attention_by_head", spy)
    got = model.apply({"params": params}, inputs, train=True)
    assert calls == [1]
    monkeypatch.setattr(sdar, "kernel_fits", lambda length, d: False)
    want = model.apply({"params": params}, inputs, train=True)
    assert calls == [1]
    np.testing.assert_allclose(got.loss, want.loss, rtol=1e-5)


# ------------------------------------------------------------ (c) the shares

def moe_weights(seed=11):
    keys = jax.random.split(jax.random.PRNGKey(seed), 5)
    h, w, e = MODEL["hidden_size"], MODEL["moe_intermediate_size"], 8
    return {
        "router": {"kernel": jax.random.normal(keys[0], (h, e))},
        "gate": {"kernel": 0.3 * jax.random.normal(keys[1], (e, h, w))},
        "up": {"kernel": 0.3 * jax.random.normal(keys[2], (e, h, w))},
        "down": {"kernel": 0.3 * jax.random.normal(keys[3], (e, w, h))},
    }, jax.random.normal(keys[4], (96, h))


def share_of(p, index, held=4):
    cut = slice(index * held, (index + 1) * held)
    return {k: (v if k == "router" else {"kernel": v["kernel"][cut]})
            for k, v in p.items()}


def test_the_shares_add_up_to_the_uncut_layer():
    """Two shares of four experts: their partial results add up to what the
    reference gives for all eight, and each is the reference's own share."""
    p, h = moe_weights()
    with jax.default_matmul_precision("highest"):
        whole = ref.experts_share(p, h, MODEL, held=8, share_index=0)
        weights_, experts = route(h, p["router"]["kernel"], 2)
        parts = []
        for index in (0, 1):
            mine = share_of(p, index)
            out, counters = experts_share(
                h, weights_, experts, mine["gate"]["kernel"],
                mine["up"]["kernel"], mine["down"]["kernel"], index, chunks=4)
            np.testing.assert_allclose(
                out, ref.experts_share(mine, h, MODEL, held=4,
                                       share_index=index),
                rtol=1e-5, atol=1e-5)
            assert counters[2] == 0
            parts.append((out, counters))
    np.testing.assert_allclose(parts[0][0] + parts[1][0], whole, rtol=1e-5,
                               atol=1e-5)
    # every pair is held by one share or the other
    assert parts[0][1][1] + parts[1][1][1] == parts[0][1][0] == 96 * 2


@pytest.mark.parametrize("chunks", [1, 4, 7])
def test_total_imbalance_drops_no_pair(chunks):
    """Every position sends both its pairs to held experts, all but a few
    to one of them: the walk covers every pair there can be, so nothing is
    dropped, whatever the number of chunks."""
    p, h = moe_weights()
    mine = share_of(p, 0)
    tokens = h.shape[0]
    experts = jnp.stack([jnp.full((tokens,), 2), jnp.where(
        jnp.arange(tokens) < 5, 0, 3)], axis=1).astype(jnp.int32)
    weights_ = jnp.tile(jnp.asarray([[0.7, 0.3]]), (tokens, 1))
    with jax.default_matmul_precision("highest"):
        out, counters = experts_share(
            h, weights_, experts, mine["gate"]["kernel"],
            mine["up"]["kernel"], mine["down"]["kernel"], 0, chunks=chunks)
        want = jnp.zeros_like(h)
        for e, w in ((2, 0.7), (0, 0.3), (3, 0.3)):
            sent = jnp.any((experts == e) & (weights_ == w), axis=1)
            y = (jax.nn.silu(h @ mine["gate"]["kernel"][e])
                 * (h @ mine["up"]["kernel"][e])) @ mine["down"]["kernel"][e]
            want = want + jnp.where(sent[:, None], w * y, 0.0)
    np.testing.assert_allclose(out, want, rtol=1e-5, atol=1e-5)
    pairs, held, dropped, load_max, load_mean = np.asarray(counters)
    assert (pairs, held, dropped) == (2 * tokens, 2 * tokens, 0)
    assert load_max == tokens and load_mean == 2 * tokens / 4


def test_held_pairs_that_fill_one_chunk_need_no_walk():
    """92 positions send one pair to a held expert, every other pair goes
    to an absent one: the held pairs and the four experts' rows of weight
    zero fill one chunk of 96 rows exactly, which the layer computes
    without walking the list."""
    p, h = moe_weights()
    mine = share_of(p, 0)
    tokens = h.shape[0]
    at = jnp.arange(tokens)
    experts = jnp.stack([jnp.where(at < tokens - 4, at % 4, 5),
                         jnp.full((tokens,), 6)], axis=1).astype(jnp.int32)
    weights_ = jnp.tile(jnp.asarray([[0.6, 0.4]]), (tokens, 1))
    with jax.default_matmul_precision("highest"):
        out, counters = experts_share(
            h, weights_, experts, mine["gate"]["kernel"],
            mine["up"]["kernel"], mine["down"]["kernel"], 0, chunks=2)
        want = jnp.zeros_like(h)
        for e in range(4):
            y = (jax.nn.silu(h @ mine["gate"]["kernel"][e])
                 * (h @ mine["up"]["kernel"][e])) @ mine["down"]["kernel"][e]
            want = want + jnp.where((experts[:, 0] == e)[:, None], 0.6 * y, 0)
    np.testing.assert_allclose(out, want, rtol=1e-5, atol=1e-5)
    assert tuple(np.asarray(counters)[:3]) == (2 * tokens, tokens - 4, 0)


@pytest.mark.parametrize("loads", [
    (0, 0, 0, 0), (0, 90, 0, 0), (23, 24, 23, 24), (96, 0, 0, 96),
], ids=["none", "one_expert", "even", "every_pair"])
def test_a_chunks_products_run_whole_whatever_the_router_sent(loads):
    """The grouped products' time follows the rows in their groups and the
    groups that have any: with a row of weight zero for every held expert
    and the rows past the held ones given to the last group, every chunk
    that is computed has all its rows in groups, and a chunk that holds
    every held pair no empty group, so a step's work does not move with
    the routing."""
    from tpu_dp.models.sdar import chunk_groups

    size = 96  # 192 pairs in two chunks, 4 rows of zero in a third
    ends = np.cumsum(np.asarray(loads) + 1)
    starts = ends - (np.asarray(loads) + 1)
    covered = 0
    for first in range(0, 3 * size, size):
        groups = np.asarray(chunk_groups(
            jnp.asarray(starts), jnp.asarray(ends), first, size))
        assert groups.sum() == size and (groups >= 0).all()
        held = np.maximum(np.minimum(ends, first + size)
                          - np.maximum(starts, first), 0)
        assert (groups[:-1] == held[:-1]).all()  # only the last one is padded
        covered += held.sum()
        if ends[-1] <= size:  # all in one chunk, as in a step that fits
            assert (groups >= 1).all() or first
    assert covered == sum(loads) + 4

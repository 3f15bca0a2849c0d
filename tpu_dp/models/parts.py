"""What the token models share (`sdar.py`, `nemotron_h.py`): RMSNorm, a
piece of chunked attention in the compiler's hands, the routers, the
dispatch of a share of the experts, and head and loss by the row.

What the TPU decides here:

- **The expert layer is told its share** (``held`` experts of the router's,
  from ``share_index * held``): it sorts the router's (position, expert)
  pairs by expert with those of absent experts last, and runs grouped
  products (`jax.lax.ragged_dot`, a tiled kernel of the TPU compiler's own)
  over the pairs it holds. What an expert computes is the caller's
  (`gated_silu`: three matrices; `squared_relu`: two), and so is the
  chunking. **No pair is dropped**: the sorted list is walked in chunks of a
  fixed size that together cover every pair there can be, and a chunk beyond
  the held pairs is skipped by `lax.cond`, so memory is bounded by a chunk.
  **A chunk's products run whole**: the kernel's time follows the rows in
  its groups and the groups that have any, so the rows past the held pairs
  go to the last group as zeros and every held expert has a row of weight
  zero; a step whose pairs fit one chunk then takes the same time whatever
  the router sent, which moves from seed to seed and from step to step.
  What the absent experts would add is left out.
- **Head and loss are one phase**, a row at a time and recomputed in the
  backward pass: the ``[rows * L, vocab]`` logits of a step never exist at
  once. A token model therefore returns a `RowLoss` and not logits.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from tpu_dp.ops import flash_block_diffusion

F32 = jnp.float32
_NEG = -0.7 * float(jnp.finfo(jnp.float32).max)

# What an expert layer counts, in the order of `experts_share`'s counters
# (float32 sums; the trainer adds them up on the device and publishes them
# where it fetches the epoch's loss). `load_max`/`load_mean` are a layer's
# largest and mean count of pairs over the held experts, `rows_run` the rows
# its grouped products ran over (chunks walked x chunk size); all summed
# over layers.
MOE_COUNTER_NAMES = (
    "moe.assignments", "moe.assignments_held", "moe.assignments_dropped",
    "moe.load_max_sum", "moe.load_mean_sum", "moe.rows_run",
)

KERNEL_TILE = flash_block_diffusion.TILE   # queries and keys a tile
# The forward kernel's output and log-sum-exp, by this name: a layer's
# recomputation keeps them, so the forward kernel runs once a step.
ATTN_SAVED = flash_block_diffusion.ATTN_SAVED


def rms_norm(x, scale, eps):
    xf = x.astype(F32)
    return scale * xf * jax.lax.rsqrt(
        jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)


# ---------------------------------------------------------------- attention

def kernel_fits(length: int, head_dim: int) -> bool:
    """Whether the attention kernels take these shapes: lanes of 128 in a
    head, whole tiles in a row (in each half of SDAR's doubled row)."""
    return head_dim % 128 == 0 and length % KERNEL_TILE == 0


def kernels_run(length: int, head_dim: int) -> bool:
    """Whether attention goes through its kernels: their shapes fit and
    they can run here (on a TPU, or inside `interpret_kernels()`)."""
    from tpu_dp.ops import _partition

    return kernel_fits(length, head_dim) and _partition.kernels_can_run()


def attention_piece(q, k, v, k_own, v_own, first: int, block: int,
                    strict: bool):
    """One row, one chunk of queries: ``q [C, kv, g, d]`` (query ``i`` is
    position ``first + i``) against the keys ``k, v [K, kv, d]`` of
    positions ``0..K-1``, visible where ``blk(j) < blk(i)`` (``strict``) or
    ``<=``, a position's block being ``position // block`` (blocks of one:
    causal); and, where given, against further keys of the query's own
    block, ``k_own, v_own [C, kv, d]`` (SDAR's noisy half). One softmax over
    both, float32."""
    c, d = q.shape[0], q.shape[-1]
    scale = 1.0 / math.sqrt(d)
    s = jnp.einsum("qhgd,khd->hgqk", q, k,
                   preferred_element_type=F32) * scale
    q_blk = (first + jnp.arange(c)) // block
    k_blk = jnp.arange(k.shape[0]) // block
    seen = (k_blk[None, :] < q_blk[:, None] if strict
            else k_blk[None, :] <= q_blk[:, None])
    s = jnp.where(seen, s, _NEG)
    top = jnp.max(s, axis=-1, keepdims=True)
    if k_own is not None:
        nb = c // block
        qb = q.reshape(nb, block, *q.shape[1:])
        kb = k_own.reshape(nb, block, *k_own.shape[1:])
        vb = v_own.reshape(nb, block, *v_own.shape[1:])
        s_own = jnp.einsum("nqhgd,nkhd->hgnqk", qb, kb,
                           preferred_element_type=F32) * scale
        s_own = s_own.reshape(*s_own.shape[:2], c, block)
        top = jnp.maximum(top, jnp.max(s_own, axis=-1, keepdims=True))
    e = jnp.exp(s - top)
    total = jnp.sum(e, axis=-1, keepdims=True)
    out = jnp.einsum("hgqk,khd->hgqd", e.astype(v.dtype), v,
                     preferred_element_type=F32)
    if k_own is not None:
        e_own = jnp.exp(s_own - top)
        total = total + jnp.sum(e_own, axis=-1, keepdims=True)
        eb = e_own.reshape(*e_own.shape[:2], nb, block, block)
        own = jnp.einsum("hgnqk,nkhd->hgnqd", eb.astype(v.dtype), vb,
                         preferred_element_type=F32)
        out = out + own.reshape(out.shape)
    out = out / total
    return jnp.moveaxis(out, 2, 0).astype(q.dtype)      # [C, kv, g, d]


def rows_piece(first: int, block: int, strict: bool, *arrays):
    """`attention_piece` over every row of ``arrays`` (each ``[rows, ...]``),
    a row at a time and recomputed in the backward pass: a chunk's scores
    are live for one row."""
    fn = jax.checkpoint(functools.partial(
        attention_piece, first=first, block=block, strict=strict))
    return jax.lax.map(lambda row: fn(*row), arrays)


# ------------------------------------------------------------------ routers

def route_softmax(h, router, top_k: int, renorm: bool = True):
    """``(weights, experts)`` ``[tokens, top_k]`` over all the router's
    experts: one matrix and a softmax; router, softmax and weights in
    float32."""
    probs = jax.nn.softmax(h.astype(F32) @ router.astype(F32), axis=-1)
    weights, experts = jax.lax.top_k(probs, top_k)
    if renorm:
        weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    return weights, experts


def route_sigmoid(h, router, bias, top_k: int, scale: float,
                  renorm: bool = True):
    """``(weights, experts)`` ``[tokens, top_k]``: scores ``sigmoid(h W)``
    over all the router's experts, the chosen ones the ``top_k`` of ``score
    + bias`` (the bias moves the choice alone, so no gradient reaches it),
    their weights the scores themselves, renormalised to sum 1, times
    ``scale``. All in float32."""
    scores = jax.nn.sigmoid(h.astype(F32) @ router.astype(F32))
    _, experts = jax.lax.top_k(scores + bias.astype(F32), top_k)
    weights = jnp.take_along_axis(scores, experts, axis=-1)
    if renorm:
        weights = weights / (jnp.sum(weights, axis=-1, keepdims=True) + 1e-20)
    return weights * scale, experts


# ------------------------------------------------------------------ experts

def gated_silu(x, kernels, groups):
    """``down(silu(gate x) * up x)`` of each row's group: ``kernels`` are
    ``gate, up [held, hidden, width]``, ``down [held, width, hidden]``."""
    gate, up, down = kernels
    act = (jax.nn.silu(jax.lax.ragged_dot(x, gate, groups))
           * jax.lax.ragged_dot(x, up, groups))
    return jax.lax.ragged_dot(act, down, groups, preferred_element_type=F32)


def squared_relu(x, kernels, groups):
    """``down(relu(up x)^2)`` of each row's group, no gate: ``kernels`` are
    ``up [held, hidden, width]``, ``down [held, width, hidden]``."""
    up, down = kernels
    act = jnp.square(jax.nn.relu(jax.lax.ragged_dot(x, up, groups)))
    return jax.lax.ragged_dot(act, down, groups, preferred_element_type=F32)


def chunk_groups(starts, ends, first, size: int):
    """How many of the rows ``first .. first + size`` each group has, the
    groups lying at ``starts .. ends`` of the sorted list. The rows past the
    last group's end go to the last group: they are zeros and are masked on
    the way out, and the products run over a whole chunk (what even routing
    fills) whatever the router sent."""
    groups = jnp.clip(jnp.minimum(ends, first + size)
                      - jnp.maximum(starts, first), 0)
    return groups.at[-1].add(size - jnp.sum(groups))


def experts_share(h, weights, experts, expert_fn, kernels, share_index: int,
                  chunks: int):
    """The held experts' part of ``MoE(h)``: ``(out [tokens, hidden] f32,
    counters)``, the counters in the order of `MOE_COUNTER_NAMES`.
    ``kernels`` are the matrices of experts ``share_index * held ...`` of the
    router's, each ``[held, ...]``, and ``expert_fn(x, kernels, groups)``
    (`gated_silu`, `squared_relu`) what an expert makes of its rows.
    ``chunks`` of whole tiles hold every pair there can be: a chunk is what
    the caller expects its share to be sent at most."""
    tokens, top_k = experts.shape
    held, dtype = kernels[0].shape[0], h.dtype
    kernels = tuple(w.astype(dtype) for w in kernels)
    pairs = tokens * top_k
    # The rows of the grouped products: the pairs, and one more row of
    # weight zero for each held expert, so that no group is ever empty.
    rows = pairs + held
    # A chunk is whole tiles of eight rows (the compiler's kernel takes no
    # other; what it falls back on gave wrong sums on the v5e, and a size
    # that is not whole tiles of 128 runs eight times slower): ``chunks``
    # of them hold the pairs, one more the rows of weight zero.
    size = -(-pairs // (8 * max(1, min(chunks, pairs)))) * 8
    chunks = -(-rows // size)
    # The rows by expert, those of absent experts last; stable, so a held
    # expert's pairs stay in order of position, its row of zero after them.
    local = experts.reshape(pairs) - share_index * held
    key = jnp.where((local >= 0) & (local < held), local, held)
    loads = jnp.sum(key[:, None] == jnp.arange(held)[None, :], axis=0,
                    dtype=jnp.int32)
    order = jnp.argsort(jnp.concatenate([key, jnp.arange(held)]),
                        stable=True)
    pair_of = jnp.minimum(order, pairs - 1)
    token_of = (pair_of // top_k).astype(jnp.int32)
    weight_of = jnp.where(order < pairs, weights.reshape(pairs)[pair_of], 0)
    ends = jnp.cumsum(loads + 1)
    starts, n_rows = ends - (loads + 1), ends[-1]
    pad = chunks * size - rows
    token_of = jnp.pad(token_of, (0, pad))
    weight_of = jnp.pad(weight_of, (0, pad))

    zeros = jnp.zeros((tokens, h.shape[-1]), F32)

    def one_chunk(first):
        """What the chunk of rows from ``first`` adds to the result."""
        at = first + jnp.arange(size)
        live = (at < n_rows)[:, None]
        tok = jax.lax.dynamic_slice(token_of, (first,), (size,))
        w = jax.lax.dynamic_slice(weight_of, (first,), (size,))
        x = jnp.where(live, h.at[tok].get(mode="promise_in_bounds"), 0)
        y = expert_fn(x, kernels, chunk_groups(starts, ends, first, size))
        y = jnp.where(live, y * w[:, None], 0.0)
        return zeros.at[tok].add(y, mode="promise_in_bounds")

    # What one chunk of the walk adds, recomputed whole in the backward
    # pass: the condition lies inside the recomputed region and the running
    # sum outside it, so the walk's backward pass keeps nothing a chunk (a
    # `cond` that is differentiated itself hands out every branch's
    # residuals, zeros from the branch not taken: the layer's input and the
    # experts' matrices once a chunk, 2.8 GB at the hybrid cell's shapes).
    @jax.checkpoint
    def contribution(first):
        return jax.lax.cond(first < n_rows, one_chunk, lambda f: zeros,
                            first)

    def walk(acc, first):
        return (acc + contribution(first),
                jnp.minimum(jnp.maximum(n_rows - first, 0), size))

    firsts = jnp.arange(chunks, dtype=jnp.int32) * size

    def all_chunks():
        return jax.lax.scan(walk, zeros, firsts)

    def first_chunk():
        # What the router sent fits one chunk: no walk, no carry handed
        # from chunk to chunk.
        out = jax.checkpoint(one_chunk)(jnp.zeros((), jnp.int32))
        return out, jnp.zeros((chunks,), jnp.int32).at[0].set(n_rows)

    if chunks > 1:
        out, done = jax.lax.cond(n_rows <= size, first_chunk, all_chunks)
    else:
        out, done = all_chunks()
    n_held = n_rows - held
    counters = jnp.stack([
        jnp.asarray(pairs, F32), n_held.astype(F32),
        (n_rows - jnp.sum(done)).astype(F32),
        jnp.max(loads).astype(F32), n_held.astype(F32) / held,
        (jnp.sum(done > 0) * size).astype(F32),
    ])
    return out, counters


# ------------------------------------------------------------ head and loss

def head_loss(hidden, scale, kernel, targets, weight, eps):
    """One row's head and loss: ``(sum_i weight_i * CE(logits_i, targets_i)
    / L, hits)``, float32 logits; ``hits`` counts the positions of weight
    above zero whose largest logit is the target."""
    logits = rms_norm(hidden, scale, eps) @ kernel
    nll = jax.nn.logsumexp(logits, axis=-1) - jnp.take_along_axis(
        logits, targets[:, None], axis=-1)[:, 0]
    hits = jnp.sum((jnp.argmax(logits, axis=-1) == targets) & (weight > 0))
    return jnp.sum(weight * nll) / targets.shape[0], hits.astype(jnp.int32)


def rows_head_loss(hidden, scale, kernel, targets, weight, eps):
    """`head_loss` of every row, ``(loss [rows], hits [rows])``: a row at a
    time, its logits recomputed in the backward pass."""
    row = jax.checkpoint(functools.partial(head_loss, eps=eps))
    with jax.named_scope("tpu_dp.head_loss"):
        return jax.lax.map(lambda r: row(r[0], scale, kernel, r[1], r[2]),
                           (hidden, targets, weight))

"""A block-diffusion mixture-of-experts decoder (the `sdar_moe` layer of
SDAR-30B-A3B-Chat, arXiv:2510.06303; training form of BD3-LM,
arXiv:2503.09573), trained through the shared step.

Pre-norm layers ``h = x + Attn(RMSNorm(x))``, ``y = h + MoE(RMSNorm(h))``:
grouped-query attention with a per-head RMSNorm of ``q`` and ``k`` and
rotate-half RoPE, under the block-diffusion mask; routed experts of which
this chip holds a share. The reference that states every equation is
`benchmark/reference_sdar.py`; `tests/test_sdar_*.py` hold the two together.

What the TPU decides here:

- **The input is ``[xt ; x0]``**, noisy half then clean half, both at
  positions ``0..L-1``. A noisy query sees the noisy keys of its own block
  and the clean keys of earlier blocks; a clean query the clean keys of its
  own and earlier blocks. The quadrant clean-query x noisy-key is never
  computed, and no ``(2L)^2`` score array exists: queries go a chunk at a
  time against the clean keys up to the chunk's end (static slices), the
  own-block noisy keys are a ``B x B`` product beside it, and the two share
  one softmax through a common maximum. A piece is wrapped in
  `jax.checkpoint` and goes a row at a time, so its scores are live for one
  row of one chunk and are recomputed in the backward pass.
- **The expert layer is told its share** (``experts_held`` of the router's
  ``num_experts``, from ``share_index * experts_held``): it routes over all,
  sorts the (position, expert) pairs by expert with those of absent experts
  last, and runs grouped products (`jax.lax.ragged_dot`, a tiled kernel of
  the TPU compiler's own) over the pairs it holds. **No pair is dropped**:
  the sorted list is walked in ``moe_chunks`` chunks of a fixed size that
  together cover every pair there can be, and a chunk beyond the held pairs
  is skipped by `lax.cond`, so memory is bounded by a chunk. **A chunk's
  products run whole**: the kernel's time follows the rows in its groups and
  the groups that have any, so the rows past the held pairs go to the last
  group as zeros and every held expert has a row of weight zero; a step
  whose pairs fit one chunk (an eighth of them, what even routing sends)
  then takes the same time whatever the router sent, which moves from seed
  to seed and from step to step. What the absent experts would add is left
  out.
- **Head and loss are one phase** over the noisy half only, a row at a time
  and recomputed in the backward pass: the ``[rows * L, vocab]`` logits of a
  step never exist at once. The model therefore returns a `RowLoss` and not
  logits.
- A layer is wrapped in `jax.checkpoint`: its input is saved in the compute
  dtype, the rest recomputed.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any

import jax
import jax.numpy as jnp

from tpu_dp.models.outputs import RowLoss

F32 = jnp.float32
_NEG = -0.7 * float(jnp.finfo(jnp.float32).max)

# What a step counts, in the order of `RowLoss.counters` (float32 sums; the
# trainer adds them up on the device and publishes them where it fetches the
# epoch's loss). `load_max`/`load_mean` are a layer's largest and mean count
# of pairs over the held experts, summed over layers.
COUNTER_NAMES = (
    "moe.assignments", "moe.assignments_held", "moe.assignments_dropped",
    "moe.load_max_sum", "moe.load_mean_sum",
    "diffusion.tokens", "diffusion.masked_tokens",
)


def rms_norm(x, scale, eps):
    xf = x.astype(F32)
    return scale * xf * jax.lax.rsqrt(
        jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)


def rope(x, positions, theta):
    """Rotate-half RoPE of ``x [..., positions, head_dim]``: the angles in
    float32, the rotation in ``x``'s own dtype (as the published
    implementation applies it to its bfloat16 heads)."""
    half = x.shape[-1] // 2
    freq = 1.0 / (theta ** (jnp.arange(half, dtype=F32) / half))
    angle = positions.astype(F32)[:, None] * freq[None, :]
    cos = jnp.concatenate([jnp.cos(angle)] * 2, -1).astype(x.dtype)
    sin = jnp.concatenate([jnp.sin(angle)] * 2, -1).astype(x.dtype)
    rotated = jnp.concatenate([-x[..., half:], x[..., :half]], -1)
    return x * cos + rotated * sin


# ---------------------------------------------------------------- attention

def _attention_piece(q, k, v, k_own, v_own, first: int, block: int,
                     strict: bool):
    """One row, one chunk of queries: ``q [C, kv, g, d]`` (query ``i`` is
    position ``first + i``) against the clean keys ``k, v [K, kv, d]`` of
    positions ``0..K-1``, visible where ``blk(j) < blk(i)`` (``strict``) or
    ``<=``; and, where given, against the noisy keys of the query's own
    block, ``k_own, v_own [C, kv, d]``. One softmax over both, float32."""
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


def block_diffusion_attention(q, k, v, block: int, chunk: int):
    """``q [rows, 2L, heads, d]``, ``k, v [rows, 2L, kv, d]`` over
    ``[noisy ; clean]`` -> ``[rows, 2L, heads, d]`` under the
    block-diffusion mask (module docstring)."""
    rows, n2, heads, d = q.shape
    length, kv = n2 // 2, k.shape[2]
    chunk = min(chunk, length)
    if length % chunk or chunk % block:
        raise ValueError(
            f"sequence length {length} must be a multiple of the attention "
            f"chunk {chunk}, and that of the block length {block}")
    q = q.reshape(rows, 2, length, kv, heads // kv, d)
    k = k.reshape(rows, 2, length, kv, d)
    v = v.reshape(rows, 2, length, kv, d)

    def piece(first, strict, *arrays):
        fn = jax.checkpoint(functools.partial(
            _attention_piece, first=first, block=block, strict=strict))
        return jax.lax.map(lambda row: fn(*row), arrays)

    noisy, clean = [], []
    for first in range(0, length, chunk):
        last = first + chunk
        k_seen, v_seen = k[:, 1, :last], v[:, 1, :last]
        noisy.append(piece(first, True, q[:, 0, first:last], k_seen, v_seen,
                           k[:, 0, first:last], v[:, 0, first:last]))
        clean.append(piece(first, False, q[:, 1, first:last], k_seen, v_seen,
                           None, None))
    out = jnp.concatenate(noisy + clean, axis=1)
    return out.reshape(rows, n2, heads, d)


# The TPU compiler's own form of the above is bound by memory, not by the
# products: every score is written, read for the maximum, read for the
# exponential and read again for the values, 16 bytes a pair and pass, three
# forward passes and a backward one a step (775 GB a step at 4 x 4,096
# tokens; PERF.md §6, PR 28). The flash kernel that ships with JAX
# (`splash_attention`) keeps a tile's scores in fast memory, skips the
# tiles the mask empties, computes the mask from the indices, and has its
# own backward kernels; it takes any mask that is a function of (query
# index, key index), so the block-diffusion mask over `[noisy ; clean]` is
# one.

KERNEL_TILE = 512   # queries and keys a tile of the kernel
# The kernel's output and row sums, by this name: a layer's recomputation
# keeps them, so the forward kernel runs once a step and not twice.
ATTN_SAVED = "tpu_dp.attn_out"


def kernel_fits(length: int, head_dim: int) -> bool:
    """Whether the shipped flash kernel takes these shapes: lanes of 128
    in a head, whole tiles in each half of the row."""
    return head_dim % 128 == 0 and length % KERNEL_TILE == 0


@functools.lru_cache(maxsize=None)
def _flash_kernel(length: int, block: int, heads_per_kv: int,
                  interpret: bool):
    """The kernel for one key/value head and the query heads it serves,
    over a row of ``2 * length`` positions."""
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_kernel as kernel_lib,
        splash_attention_mask as mask_lib,
    )

    class BlockDiffusionMask(mask_lib._ComputableMask):
        """``seen(i, j)`` over ``[noisy ; clean]``, from the indices alone
        (numpy arrays when the tiles are sorted into empty, full and
        partial, traced ones inside the kernel)."""

        def __init__(self):
            def seen(q, k):
                q_noisy, k_noisy = q < length, k < length
                q_blk = (q - length * (q >= length)) // block
                k_blk = (k - length * (k >= length)) // block
                return ((q_noisy & k_noisy & (q_blk == k_blk))
                        | (q_noisy & ~k_noisy & (k_blk < q_blk))
                        | (~q_noisy & ~k_noisy & (k_blk <= q_blk)))

            super().__init__((2 * length, 2 * length), seen)

        def __eq__(self, other):
            return isinstance(other, type(self)) and self.shape == other.shape

        def __hash__(self):
            return hash((type(self).__name__, self.shape, length, block))

    tile = KERNEL_TILE
    sizes = kernel_lib.BlockSizes(
        block_q=tile, block_kv=tile, block_kv_compute=tile,
        block_q_dkv=tile, block_kv_dkv=tile, block_kv_dkv_compute=tile,
        use_fused_bwd_kernel=True)
    with jax.ensure_compile_time_eval():
        return kernel_lib.make_splash_mqa_single_device(
            mask=mask_lib.MultiHeadMask([BlockDiffusionMask()] * heads_per_kv),
            block_sizes=sizes, interpret=interpret,
            residual_checkpoint_name=ATTN_SAVED)


def flash_attention_by_head(q, k, v, block: int, interpret: bool):
    """The shipped flash kernel under the block-diffusion mask, in the
    layout it takes: ``q [rows, kv, heads / kv, 2L, d]``, ``k, v [rows, kv,
    2L, d]`` -> ``[rows, kv, heads / kv, 2L, d]``."""
    kernel = _flash_kernel(q.shape[3] // 2, block, q.shape[2], interpret)
    q = (q * (1.0 / math.sqrt(q.shape[-1]))).astype(q.dtype)
    return jax.vmap(jax.vmap(kernel))(q, k, v)


def flash_block_diffusion_attention(q, k, v, block: int, interpret: bool):
    """`block_diffusion_attention` through the shipped flash kernel; same
    arguments and result."""
    rows, n2, heads, d = q.shape
    kv = k.shape[2]
    q = q.reshape(rows, n2, kv, heads // kv, d).transpose(0, 2, 3, 1, 4)
    out = flash_attention_by_head(q, k.transpose(0, 2, 1, 3),
                                  v.transpose(0, 2, 1, 3), block, interpret)
    return out.transpose(0, 3, 1, 2, 4).reshape(rows, n2, heads, d)


# ------------------------------------------------------------------ experts

def route(h, router, top_k: int, renorm: bool = True):
    """``(weights, experts)`` ``[tokens, top_k]`` over all the router's
    experts; router, softmax and weights in float32."""
    probs = jax.nn.softmax(h.astype(F32) @ router.astype(F32), axis=-1)
    weights, experts = jax.lax.top_k(probs, top_k)
    if renorm:
        weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    return weights, experts


def chunk_groups(starts, ends, first, size: int):
    """How many of the rows ``first .. first + size`` each group has, the
    groups lying at ``starts .. ends`` of the sorted list. The rows past the
    last group's end go to the last group: they are zeros and are masked on
    the way out, and the products run over a whole chunk (what even routing
    fills) whatever the router sent."""
    groups = jnp.clip(jnp.minimum(ends, first + size)
                      - jnp.maximum(starts, first), 0)
    return groups.at[-1].add(size - jnp.sum(groups))


def experts_share(h, weights, experts, gate, up, down, share_index: int,
                  chunks: int):
    """The held experts' part of ``MoE(h)``: ``(out [tokens, hidden] f32,
    counters)``. ``gate, up [held, hidden, width]``, ``down [held, width,
    hidden]`` are experts ``share_index * held ...`` of the router's."""
    tokens, top_k = experts.shape
    held, dtype = gate.shape[0], h.dtype
    gate, up, down = (w.astype(dtype) for w in (gate, up, down))
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

    def one_chunk(acc, first):
        at = first + jnp.arange(size)
        live = (at < n_rows)[:, None]
        tok = jax.lax.dynamic_slice(token_of, (first,), (size,))
        w = jax.lax.dynamic_slice(weight_of, (first,), (size,))
        x = jnp.where(live, h.at[tok].get(mode="promise_in_bounds"), 0)
        groups = chunk_groups(starts, ends, first, size)
        act = (jax.nn.silu(jax.lax.ragged_dot(x, gate, groups))
               * jax.lax.ragged_dot(x, up, groups))
        y = jax.lax.ragged_dot(act, down, groups,
                               preferred_element_type=F32)
        y = jnp.where(live, y * w[:, None], 0.0)
        return acc.at[tok].add(y, mode="promise_in_bounds")

    def walk(acc, first):
        acc = jax.lax.cond(first < n_rows, jax.checkpoint(one_chunk),
                           lambda a, _: a, acc, first)
        return acc, jnp.minimum(jnp.maximum(n_rows - first, 0), size)

    firsts = jnp.arange(chunks, dtype=jnp.int32) * size

    def all_chunks():
        return jax.lax.scan(walk, jnp.zeros((tokens, h.shape[-1]), F32),
                            firsts)

    def first_chunk():
        # What the router sent fits one chunk: no walk, no carry handed
        # from chunk to chunk.
        zero = jnp.zeros((), jnp.int32)
        out = jax.checkpoint(one_chunk)(
            jnp.zeros((tokens, h.shape[-1]), F32), zero)
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
    ])
    return out, counters


# -------------------------------------------------------------------- model

def decoder_layer(p, x, positions, m: "BlockDiffusionMoE"):
    """``(y, counters)``: one pre-norm layer on ``x [rows, 2L, hidden]``."""
    rows, n2, _ = x.shape
    nq, nkv, d, dtype = m.num_heads, m.num_kv_heads, m.head_dim, m.dtype
    attn, moe = p["attn"], p["moe"]
    with jax.named_scope("tpu_dp.attn"):
        # Heads first from the projection on (the flash kernel's layout,
        # and one less pass over q than turning it afterwards); the norms'
        # sums in float32, their results and RoPE in the compute dtype.
        y = rms_norm(x, p["attn_norm"]["scale"], m.eps).astype(dtype)
        g, hidden = nq // nkv, x.shape[-1]
        wq = attn["q_proj"]["kernel"].astype(dtype).reshape(hidden, nkv, g, d)
        wk = attn["k_proj"]["kernel"].astype(dtype).reshape(hidden, nkv, d)
        wv = attn["v_proj"]["kernel"].astype(dtype).reshape(hidden, nkv, d)
        wo = attn["o_proj"]["kernel"].astype(dtype).reshape(nkv, g, d, hidden)
        q = jnp.einsum("rnh,hkgd->rkgnd", y, wq)
        k = jnp.einsum("rnh,hkd->rknd", y, wk)
        v = jnp.einsum("rnh,hkd->rknd", y, wv)
        q = rope(rms_norm(q, attn["q_norm"]["scale"], m.eps).astype(dtype),
                 positions, m.rope_theta)
        k = rope(rms_norm(k, attn["k_norm"]["scale"], m.eps).astype(dtype),
                 positions, m.rope_theta)
        x = x + jnp.einsum("rkgnd,kgdh->rnh", m.attention(q, k, v), wo)
    flat = rms_norm(x, p["moe_norm"]["scale"], m.eps).reshape(rows * n2, -1)
    with jax.named_scope("tpu_dp.moe_route"):
        weights, experts = route(flat, moe["router"]["kernel"],
                                 m.experts_per_token, m.norm_topk_prob)
    with jax.named_scope("tpu_dp.moe_experts"):
        out, counters = experts_share(
            flat.astype(dtype), weights, experts, moe["gate"]["kernel"],
            moe["up"]["kernel"], moe["down"]["kernel"], m.share_index,
            m.moe_chunks)
    return x + out.reshape(x.shape).astype(dtype), counters


def head_loss(hidden, scale, kernel, x0, weight, eps):
    """One row's head and loss over the noisy half: ``(sum_i weight_i *
    CE(logits_i, x0_i) / L, hits)``, float32; ``hits`` counts the masked
    positions whose largest logit is the clean token."""
    logits = rms_norm(hidden, scale, eps) @ kernel
    nll = jax.nn.logsumexp(logits, axis=-1) - jnp.take_along_axis(
        logits, x0[:, None], axis=-1)[:, 0]
    hits = jnp.sum((jnp.argmax(logits, axis=-1) == x0) & (weight > 0))
    return jnp.sum(weight * nll) / x0.shape[0], hits.astype(jnp.int32)


@dataclasses.dataclass(frozen=True)
class BlockDiffusionMoE:
    """The decoder as the step sees a model: ``init(rng, sample,
    train=False) -> {"params": tree}`` and ``apply({"params": tree},
    inputs, train=...) -> RowLoss``. ``inputs`` is what `make_noise_fn`'s
    function makes of a batch of token rows: ``{"xt", "x0", "weight"}``,
    each ``[rows, L]`` (bare rows stand for themselves, unmasked).

    ``num_classes`` is the vocabulary held here; the mask token is its last
    id. Shapes default to the published widths of SDAR-30B-A3B-Chat and this
    chip's share of an eight-chip expert group."""

    num_classes: int
    dtype: Any = jnp.float32
    hidden_size: int = 2048
    num_layers: int = 4
    num_heads: int = 32
    num_kv_heads: int = 4
    head_dim: int = 128
    expert_width: int = 768
    num_experts: int = 128         # the router's width
    experts_per_token: int = 8
    experts_held: int = 16         # of num_experts, from share_index * held
    share_index: int = 0
    block_length: int = 4
    rope_theta: float = 1e6
    eps: float = 1e-6
    init_std: float = 0.02
    norm_topk_prob: bool = True
    attn_chunk: int = 512          # queries a piece of attention
    moe_chunks: int = 8            # chunks that cover every pair there can be

    counter_names = COUNTER_NAMES
    count_counter = "diffusion.masked_tokens"   # what `correct` is a share of

    def attention(self, q, k, v):
        """Attention under the block-diffusion mask: ``q [rows, kv, heads /
        kv, 2L, d]``, ``k, v [rows, kv, 2L, d]`` -> ``q``'s shape. Through
        the shipped flash kernel wherever its shapes fit and it can run (on
        a TPU, or inside `interpret_kernels()`), else by the compiler's own
        products (on the chip 1.46 s a step against 1.10: PERF.md §6, PR
        28)."""
        from tpu_dp.ops import _partition

        if kernel_fits(q.shape[3] // 2, self.head_dim) and (
                jax.default_backend() == "tpu"
                or bool(_partition._interpret_requests)):
            return flash_attention_by_head(q, k, v, self.block_length,
                                           _partition.interpret())
        rows, kv, g, n2, d = q.shape
        out = block_diffusion_attention(
            q.transpose(0, 3, 1, 2, 4).reshape(rows, n2, kv * g, d),
            k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3),
            self.block_length, self.attn_chunk)
        return out.reshape(rows, n2, kv, g, d).transpose(0, 2, 3, 1, 4)

    @property
    def mask_id(self) -> int:
        return self.num_classes - 1

    def make_noise_fn(self, seed: int):
        from tpu_dp.data.noise import make_block_noise_fn

        return make_block_noise_fn(seed, self.block_length, self.mask_id)

    def _shapes(self) -> dict:
        h, d, w = self.hidden_size, self.head_dim, self.expert_width
        nq, nkv, held = self.num_heads, self.num_kv_heads, self.experts_held
        layer = {
            "attn_norm": {"scale": (h,)}, "moe_norm": {"scale": (h,)},
            "attn": {"q_proj": {"kernel": (h, nq * d)},
                     "k_proj": {"kernel": (h, nkv * d)},
                     "v_proj": {"kernel": (h, nkv * d)},
                     "o_proj": {"kernel": (nq * d, h)},
                     "q_norm": {"scale": (d,)}, "k_norm": {"scale": (d,)}},
            "moe": {"router": {"kernel": (h, self.num_experts)},
                    "gate": {"kernel": (held, h, w)},
                    "up": {"kernel": (held, h, w)},
                    "down": {"kernel": (held, w, h)}},
        }
        shapes = {"embed": {"embedding": (self.num_classes, h)},
                  "final_norm": {"scale": (h,)},
                  "head": {"kernel": (h, self.num_classes)}}
        shapes.update({f"layers_{i}": layer for i in range(self.num_layers)})
        return shapes

    def init(self, rng, sample=None, train: bool = False) -> dict:
        """normal(0, init_std) matrices, norm weights 1; drawn in one
        program (``sample`` is not run through the model)."""
        shapes = self._shapes()
        leaves, treedef = jax.tree_util.tree_flatten_with_path(
            shapes, is_leaf=lambda x: isinstance(x, tuple))

        def draw(rng):
            out = []
            for i, (path, shape) in enumerate(leaves):
                if path[-1].key == "scale":
                    out.append(jnp.ones(shape, F32))
                else:
                    out.append(self.init_std * jax.random.normal(
                        jax.random.fold_in(rng, i), shape, F32))
            return jax.tree_util.tree_unflatten(treedef, out)

        return {"params": jax.jit(draw)(rng)}

    def apply(self, variables, inputs, train: bool = False, mutable=None):
        params = variables["params"]
        if not isinstance(inputs, dict):
            inputs = {"xt": inputs, "x0": inputs,
                      "weight": jnp.zeros(inputs.shape, F32)}
        xt, x0, weight = inputs["xt"], inputs["x0"], inputs["weight"]
        rows, length = x0.shape
        positions = jnp.concatenate([jnp.arange(length)] * 2)
        ids = jnp.concatenate([xt, x0], axis=1)
        x = params["embed"]["embedding"].at[ids].get(
            mode="promise_in_bounds").astype(self.dtype)
        layer = jax.checkpoint(
            functools.partial(decoder_layer, m=self),
            policy=jax.checkpoint_policies.save_only_these_names(ATTN_SAVED))
        counters = jnp.zeros((5,), F32)
        for i in range(self.num_layers):
            x, c = layer(params[f"layers_{i}"], x, positions)
            counters = counters + c
        with jax.named_scope("tpu_dp.head_loss"):
            row = jax.checkpoint(functools.partial(head_loss, eps=self.eps))
            loss, hits = jax.lax.map(
                lambda r: row(r[0], params["final_norm"]["scale"],
                              params["head"]["kernel"], r[1], r[2]),
                (x[:, :length], x0, weight))
        masked = jnp.sum(weight > 0, axis=1).astype(jnp.int32)
        counters = jnp.concatenate([counters, jnp.stack([
            jnp.asarray(rows * length, F32), jnp.sum(masked).astype(F32)])])
        return RowLoss(loss=loss, correct=hits, count=masked,
                       counters=counters)

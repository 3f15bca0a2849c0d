"""A hybrid state-space / attention / mixture-of-experts decoder (the
`nemotron_h` layers of NVIDIA-Nemotron-3-Nano-30B-A3B), trained on the plain
next-token objective through the shared step.

Every layer is ``h = h + Mixer(RMSNorm(h))`` with *one* mixer, chosen by the
layer's letter in ``layer_pattern``: ``M`` a Mamba-2 state-space mixer, ``*``
causal grouped-query attention (no positional encoding, no q/k norm), ``E`` a
mixture of experts (a sigmoid router with a selection bias over all the
router's experts, squared-ReLU experts without a gate of which this chip
holds a share, and one shared expert that every token takes). The reference
that states every equation is `benchmark/reference_nemotron_h.py`;
`tests/test_nemotron_*.py` hold the two together.

What the TPU decides here:

- **The state-space recurrence runs in its chunked form**: within a chunk
  of ``ssm_chunk`` positions the output is a masked product (``C B^T`` of a
  group, times the heads' decay between the two positions), between chunks
  a carried state of ``[heads, head_dim, state]``. The result does not
  depend on the chunk; 128 is the MXU's tile. Decays, cumulative sums,
  ``softplus``, the carried state and the gated norm are float32, the
  products' operands the compute dtype. **Through a kernel pair wherever
  `ssd_scan.runs`** (`tpu_dp/ops/ssd_scan.py`: on a TPU or inside
  `interpret_kernels()`, rows of whole chunks of 128, a state of whole 128
  lanes, a group's heads whole lanes; the published widths): a chunk's
  decay matrix, its ``C B^T`` and the carried state stay in fast memory,
  where the compiler's form (`ssd_chunked`, every other shape and backend,
  and the tests' oracle) makes some thirty passes a layer over arrays of
  ``[tokens, heads, 128]`` float32. The choice is made at trace time from
  the shapes and the backend. In `ssd_chunked` a row that is not whole
  chunks is padded at its end with steps of ``delta = 0``, which neither
  decay nor add to the state, and the padding's outputs are cut off.
- **Attention goes through the flash kernel pair** under its causal mask
  wherever `parts.kernels_run` (`tpu_dp/ops/flash_block_diffusion.py`: at
  8,192 positions the compiler's own full scores would be 17 GB), and
  chunked by queries in the compiler's hands elsewhere, as SDAR's does.
- **The expert layer** is `parts.experts_share` with `parts.squared_relu`;
  the shared expert is two dense products beside it, computed once whatever
  the share.
- A layer is wrapped in `jax.checkpoint` (`run_layer`): its input is saved
  in the compute dtype, the rest recomputed, every row at once: with the
  scan's temporaries in fast memory a loop over rows costs more in its own
  buffers than it saves (PERF.md §6, PR 35). Head and loss go a row at a time
  (`parts.rows_head_loss`): position ``t`` is scored against token ``t + 1``,
  the last position against nothing.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any

import jax
import jax.numpy as jnp

from tpu_dp.models import parts
from tpu_dp.models.outputs import RowLoss
from tpu_dp.models.parts import F32, rms_norm
from tpu_dp.ops import ssd_scan
from tpu_dp.ops.flash_block_diffusion import CausalMask, flash_attention

COUNTER_NAMES = parts.MOE_COUNTER_NAMES + ("lm.tokens",)
MIXERS = {"M": "ssm", "*": "attn", "E": "moe"}
EXPERT_LANES = 256   # the routed experts' width is padded to whole ones


# -------------------------------------------------------------- state space

def causal_conv(x, kernel, bias):
    """Depthwise causal convolution along the positions: ``x [rows, L,
    channels]``, ``kernel [taps, channels]``, zeros left of the row; the
    last tap is the position's own. float32."""
    taps, length = kernel.shape[0], x.shape[1]
    padded = jnp.pad(x.astype(F32), ((0, 0), (taps - 1, 0), (0, 0)))
    out = bias.astype(F32)
    for j in range(taps):
        out = out + kernel[j].astype(F32) * padded[:, j:j + length]
    return out


def ssd_chunked(x, delta, a_head, b, c, chunk: int):
    """The selective state-space recurrence in its chunked form.

    ``x [rows, L, heads, P]``, ``b, c [rows, L, groups, N]`` (head ``h``
    reads group ``h // (heads / groups)``) in the compute dtype, ``delta
    [rows, L, heads]`` and ``a_head [heads]`` (negative) float32. With
    ``S_t = exp(delta_t a) S_{t-1} + delta_t x_t (outer) b_t`` from ``S_{-1}
    = 0``, returns ``y_t = S_t c_t``, ``[rows, L, heads, P]`` float32."""
    rows, length, heads, p = x.shape
    groups, n = b.shape[-2:]
    r, dtype = heads // groups, x.dtype
    q = min(chunk, length)
    pad = -length % q
    if pad:
        x, delta, b, c = (
            jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
            for t in (x, delta, b, c))
    nc = (length + pad) // q
    x = x.reshape(rows, nc, q, groups, r, p)
    b = b.reshape(rows, nc, q, groups, n)
    c = c.reshape(rows, nc, q, groups, n)
    # heads along the lanes' side for the per-position scalars: [.., g, r, q]
    delta = jnp.moveaxis(delta.reshape(rows, nc, q, groups, r), 2, -1)
    cum = jnp.cumsum(delta * a_head.reshape(groups, r, 1), axis=-1)
    # Within a chunk:
    # y_i = sum_{j <= i} exp(cum_i - cum_j) delta_j (c_i . b_j) x_j
    cb = jnp.einsum("bcign,bcjgn->bcgij", c, b, preferred_element_type=F32)
    at = jnp.arange(q)
    seg = jnp.where(at[:, None] >= at[None, :],
                    cum[..., :, None] - cum[..., None, :], -jnp.inf)
    w = cb[:, :, :, None] * jnp.exp(seg) * delta[..., None, :]
    y = jnp.einsum("bcgrij,bcjgrp->bcigrp", w.astype(dtype), x,
                   preferred_element_type=F32)
    # What a chunk adds to the state it hands on, and how far it decays
    # the state it was handed.
    to_end = jnp.exp(cum[..., -1:] - cum) * delta            # [.., g, r, q]
    xw = (x.astype(F32) * jnp.moveaxis(to_end, -1, 2)[..., None]).astype(dtype)
    local = jnp.einsum("bcjgrp,bcjgn->bcgrpn", xw, b,
                       preferred_element_type=F32)
    chunk_decay = jnp.exp(cum[..., -1])                      # [rows, nc, g, r]

    def carry(state, xs):
        decay, added = xs
        return decay[..., None, None] * state + added, state

    _, entering = jax.lax.scan(
        carry, jnp.zeros((rows, groups, r, p, n), F32),
        (jnp.moveaxis(chunk_decay, 1, 0), jnp.moveaxis(local, 1, 0)))
    entering = jnp.moveaxis(entering, 0, 1)   # the state a chunk is handed
    y_in = jnp.einsum("bcign,bcgrpn->bcigrp", c, entering.astype(dtype),
                      preferred_element_type=F32)
    y = y + y_in * jnp.moveaxis(jnp.exp(cum), -1, 2)[..., None]
    return y.reshape(rows, nc * q, heads, p)[:, :length]


def ssm_mixer(p, u, m: "NemotronH"):
    """The Mamba-2 mixer on ``u [rows, L, hidden]`` (compute dtype).

    What is ``[.., inner]`` wide after the scan (``y``, the gate ``z``, the
    norm's result) is held a group's channels at a time, ``[rows, L, groups,
    width]`` in the compiler's hands and ``[rows, groups, L, width]`` where
    the scan's kernels run, which write ``y`` so: the grouped norm then
    reduces over the last axis of every array as it stands, ``z`` is born
    in that layout from a product of its own columns of ``in_proj`` and the
    output projection contracts groups and channels. (With ``y`` as ``[rows,
    L, inner]`` from the kernels the norm's reshape was a relayout of the
    whole array, 21 ms a step at the published widths; PERF.md §6, PR 35.)"""
    rows, length, _ = u.shape
    heads, hp, n = m.ssm_heads, m.ssm_head_dim, m.ssm_state
    groups, dtype = m.ssm_groups, m.dtype
    inner, gn = heads * hp, groups * n
    width = inner // groups
    by_kernels = ssd_scan.runs(length, m.ssm_chunk, heads, hp, groups, n)
    grouped = "rgld" if by_kernels else "rlgd"
    with jax.named_scope("tpu_dp.ssm_proj"):
        kernel = p["in_proj"]["kernel"].astype(dtype)
        z = jnp.einsum(f"rlh,hgd->{grouped}", u,
                       kernel[:, :inner].reshape(-1, groups, width))
        xbc, dt = jnp.split(u @ kernel[:, inner:], [inner + 2 * gn], axis=-1)
    with jax.named_scope("tpu_dp.ssm_conv"):
        xbc = jax.nn.silu(causal_conv(xbc, p["conv"]["kernel"],
                                      p["conv"]["bias"])).astype(dtype)
        x, b, c = jnp.split(xbc, [inner, inner + gn], axis=-1)
    with jax.named_scope("tpu_dp.ssm_scan"):
        delta = jax.nn.softplus(dt.astype(F32) + p["dt_bias"])
        a_head = -jnp.exp(p["A_log"].astype(F32))
        if by_kernels:
            y = ssd_scan.ssd_scan(x, delta, a_head, b, c, p["D"], groups)
        else:
            x = x.reshape(rows, length, heads, hp)
            y = ssd_chunked(x, delta, a_head,
                            b.reshape(rows, length, groups, n),
                            c.reshape(rows, length, groups, n), m.ssm_chunk)
            y = y + p["D"].astype(F32)[:, None] * x.astype(F32)
            y = y.reshape(rows, length, groups, width)
    with jax.named_scope("tpu_dp.ssm_norm"):
        # gate first, then an RMSNorm over each group's channels
        scale = p["norm"]["scale"].reshape(groups, width)
        y = y * jax.nn.silu(z.astype(F32))
        y = y * jax.lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True) + m.eps)
        y = (y * (scale[:, None] if by_kernels else scale)).astype(dtype)
    with jax.named_scope("tpu_dp.ssm_proj"):
        out = p["out_proj"]["kernel"].astype(dtype)
        return jnp.einsum(f"{grouped},gdh->rlh", y,
                          out.reshape(groups, width, -1))


# ---------------------------------------------------------------- attention

def causal_attention(q, k, v, chunk: int):
    """``q [rows, L, kv, g, d]``, ``k, v [rows, L, kv, d]`` -> ``q``'s
    shape: softmax over the keys at or before the query, a chunk of queries
    at a time against the keys up to the chunk's end (static slices), a row
    at a time and recomputed in the backward pass."""
    length = q.shape[1]
    chunk = min(chunk, length)
    if length % chunk:
        raise ValueError(f"sequence length {length} must be a multiple of "
                         f"the attention chunk {chunk}")
    pieces = [parts.rows_piece(first, 1, False, q[:, first:first + chunk],
                               k[:, :first + chunk], v[:, :first + chunk],
                               None, None)
              for first in range(0, length, chunk)]
    return jnp.concatenate(pieces, axis=1)


def attention_mixer(p, u, m: "NemotronH"):
    """Causal grouped-query attention on ``u [rows, L, hidden]``: no bias,
    no positional encoding, no q/k norm; query head ``i`` reads key/value
    head ``i // (heads / kv)``."""
    length, hidden = u.shape[1:]
    nq, nkv, d, dtype = m.num_heads, m.num_kv_heads, m.head_dim, m.dtype
    g = nq // nkv
    wq = p["q_proj"]["kernel"].astype(dtype).reshape(hidden, nkv, g, d)
    wk = p["k_proj"]["kernel"].astype(dtype).reshape(hidden, nkv, d)
    wv = p["v_proj"]["kernel"].astype(dtype).reshape(hidden, nkv, d)
    wo = p["o_proj"]["kernel"].astype(dtype).reshape(nkv, g, d, hidden)
    if parts.kernels_run(length, d):
        # heads major, as the flash pair reads them; the scores' scale goes
        # into q before its one rounding
        q = jnp.einsum("rnh,hkgd->rkgnd", u, wq, preferred_element_type=F32)
        q = (q * (1.0 / math.sqrt(d))).astype(dtype)
        k = jnp.einsum("rnh,hkd->rknd", u, wk)
        v = jnp.einsum("rnh,hkd->rknd", u, wv)
        out = flash_attention(q, k, v, CausalMask(length))
        return jnp.einsum("rkgnd,kgdh->rnh", out, wo)
    q = jnp.einsum("rnh,hkgd->rnkgd", u, wq)
    k = jnp.einsum("rnh,hkd->rnkd", u, wk)
    v = jnp.einsum("rnh,hkd->rnkd", u, wv)
    out = causal_attention(q, k, v, m.attn_chunk)
    return jnp.einsum("rnkgd,kgdh->rnh", out, wo)


# ------------------------------------------------------------------ experts

def moe_mixer(p, u, m: "NemotronH"):
    """``(routed + shared, counters)`` on ``u [rows, L, hidden]`` float32
    (the norm's result: the router reads it unrounded)."""
    dtype = m.dtype
    flat = u.reshape(-1, u.shape[-1])
    with jax.named_scope("tpu_dp.moe_route"):
        weights, experts = parts.route_sigmoid(
            flat, p["router"]["kernel"], p["router"]["bias"],
            m.experts_per_token, m.routed_scaling, m.norm_topk_prob)
    flat = flat.astype(dtype)
    with jax.named_scope("tpu_dp.moe_experts"):
        # The compiler's grouped product runs a width of whole 256 lanes
        # at twice the speed of 1,856 (a chunk of 12,288 rows, forward and
        # backward: 24.1 ms at 1,856 or 1,920, 12.2 at 2,048, 12.5 padded;
        # PERF.md §6, PR 34): columns of zeros, whose squared ReLU is
        # zero, against rows of zeros.
        extra = -m.expert_width % EXPERT_LANES
        up = jnp.pad(p["up"]["kernel"], ((0, 0), (0, 0), (0, extra)))
        down = jnp.pad(p["down"]["kernel"], ((0, 0), (0, extra), (0, 0)))
        routed, counters = parts.experts_share(
            flat, weights, experts, parts.squared_relu, (up, down),
            m.share_index, m.moe_chunks)
    with jax.named_scope("tpu_dp.moe_shared"):
        act = jnp.square(jax.nn.relu(
            flat @ p["shared_up"]["kernel"].astype(dtype)))
        shared = jnp.dot(act, p["shared_down"]["kernel"].astype(dtype),
                         preferred_element_type=F32)
    return (routed + shared).reshape(u.shape), counters


# -------------------------------------------------------------------- model

def layer(p, x, kind: str, m: "NemotronH"):
    """``(y, counters)``: one pre-norm layer of mixer ``kind`` (`MIXERS`) on
    ``x [rows, L, hidden]``."""
    u = rms_norm(x, p["norm"]["scale"], m.eps)
    counters = jnp.zeros((len(parts.MOE_COUNTER_NAMES),), F32)
    with jax.named_scope(f"tpu_dp.{kind}"):
        if kind == "moe":
            out, counters = moe_mixer(p["mixer"], u, m)
        elif kind == "ssm":
            out = ssm_mixer(p["mixer"], u.astype(m.dtype), m)
        else:
            out = attention_mixer(p["mixer"], u.astype(m.dtype), m)
    return x + out.astype(m.dtype), counters


def run_layer(p, x, kind: str, m: "NemotronH"):
    """`layer` under `jax.checkpoint`: its input is saved, the rest
    recomputed; the flash kernel's output is kept."""
    return jax.checkpoint(
        functools.partial(layer, kind=kind, m=m),
        policy=jax.checkpoint_policies.save_only_these_names(
            parts.ATTN_SAVED))(p, x)


def _inverse_softplus(x):
    return x + jnp.log(-jnp.expm1(-x))


@dataclasses.dataclass(frozen=True)
class NemotronH:
    """The decoder as the step sees a model: ``init(rng, sample,
    train=False) -> {"params": tree}`` and ``apply({"params": tree}, tokens,
    train=...) -> RowLoss``; ``tokens [rows, L]`` are the input and, shifted
    by one, the targets (no noise: the model has no `make_noise_fn`).

    ``num_classes`` is the vocabulary held here. Shapes default to the
    published widths of NVIDIA-Nemotron-3-Nano-30B-A3B, the first nine
    layers of its pattern and this chip's share of a sixteen-chip expert
    group."""

    num_classes: int
    dtype: Any = jnp.float32
    hidden_size: int = 2688
    layer_pattern: str = "MEMEM*EME"   # a letter a layer: M, E or *
    ssm_heads: int = 64
    ssm_head_dim: int = 64
    ssm_state: int = 128
    ssm_groups: int = 8
    conv_kernel: int = 4
    num_heads: int = 32
    num_kv_heads: int = 2
    head_dim: int = 128
    expert_width: int = 1856
    shared_expert_width: int = 3712
    num_experts: int = 128         # the router's width
    experts_per_token: int = 6
    experts_held: int = 8          # of num_experts, from share_index * held
    share_index: int = 0
    routed_scaling: float = 2.5
    norm_topk_prob: bool = True
    eps: float = 1e-5
    init_std: float = 0.02
    ssm_chunk: int = 128           # positions a chunk of the scan
    attn_chunk: int = 512          # queries a piece of attention (no kernel)
    # Chunks that cover every pair there can be: a chunk is a quarter of
    # them, four times what even routing sends a sixteenth of the experts.
    # The first expert layer's router floods the held experts in waves (2
    # to 17% of its pairs over a run's first hundred steps, the other
    # layers under 2%), and a step that walks a second chunk takes 54 ms
    # more: at an eighth, 44 of one seed's 64 timed steps did and 11 of
    # another's (PERF.md §6, PR 34).
    moe_chunks: int = 4

    counter_names = COUNTER_NAMES
    count_counter = "lm.tokens"    # what `correct` is a share of

    def __post_init__(self):
        unknown = set(self.layer_pattern) - set(MIXERS)
        if unknown or not self.layer_pattern:
            raise ValueError(
                f"layer_pattern {self.layer_pattern!r}: a letter a layer, "
                f"of {sorted(MIXERS)}")

    def _shapes(self) -> dict:
        h, d = self.hidden_size, self.head_dim
        inner = self.ssm_heads * self.ssm_head_dim
        conv = inner + 2 * self.ssm_groups * self.ssm_state
        nq, nkv, held = self.num_heads, self.num_kv_heads, self.experts_held
        w, ws = self.expert_width, self.shared_expert_width
        mixers = {
            "M": {"in_proj": {"kernel": (h, inner + conv + self.ssm_heads)},
                  "conv": {"kernel": (self.conv_kernel, conv),
                           "bias": (conv,)},
                  "dt_bias": (self.ssm_heads,), "A_log": (self.ssm_heads,),
                  "D": (self.ssm_heads,), "norm": {"scale": (inner,)},
                  "out_proj": {"kernel": (inner, h)}},
            "*": {"q_proj": {"kernel": (h, nq * d)},
                  "k_proj": {"kernel": (h, nkv * d)},
                  "v_proj": {"kernel": (h, nkv * d)},
                  "o_proj": {"kernel": (nq * d, h)}},
            "E": {"router": {"kernel": (h, self.num_experts),
                             "bias": (self.num_experts,)},
                  "up": {"kernel": (held, h, w)},
                  "down": {"kernel": (held, w, h)},
                  "shared_up": {"kernel": (h, ws)},
                  "shared_down": {"kernel": (ws, h)}},
        }
        shapes = {"embed": {"embedding": (self.num_classes, h)},
                  "final_norm": {"scale": (h,)},
                  "head": {"kernel": (h, self.num_classes)}}
        for i, letter in enumerate(self.layer_pattern):
            shapes[f"layers_{i}"] = {"norm": {"scale": (h,)},
                                     "mixer": mixers[letter]}
        return shapes

    def init(self, rng, sample=None, train: bool = False) -> dict:
        """Matrices normal(0, init_std), the two projections that write to
        the residual stream over ``sqrt(layers)``; norm weights 1; the
        convolution uniform(+-1 / sqrt(taps)) in weight and bias; ``A_log =
        log(1..heads)``, ``D = 1``, ``dt_bias`` the inverse softplus of a
        log-uniform draw in [0.001, 0.1] floored at 1e-4; the router's bias
        0. Drawn in one program (``sample`` is not run through the model)."""
        leaves, treedef = jax.tree_util.tree_flatten_with_path(
            self._shapes(), is_leaf=lambda x: isinstance(x, tuple))
        layers = len(self.layer_pattern)

        def draw_leaf(key, path, shape):
            names = [k.key for k in path]
            name = names[-2] if names[-1] in ("kernel", "bias") else names[-1]
            if names[-1] == "scale" or name == "D":
                return jnp.ones(shape, F32)
            if name == "A_log":
                return jnp.log(jnp.arange(1, shape[0] + 1, dtype=F32))
            if name == "dt_bias":
                dt = jnp.exp(jax.random.uniform(key, shape, F32)
                             * (math.log(0.1) - math.log(0.001))
                             + math.log(0.001))
                return _inverse_softplus(jnp.maximum(dt, 1e-4))
            if name == "conv":
                bound = 1.0 / math.sqrt(self.conv_kernel)
                return jax.random.uniform(key, shape, F32, -bound, bound)
            if names[-1] == "bias":
                return jnp.zeros(shape, F32)
            std = self.init_std
            if name in ("out_proj", "o_proj"):
                std = std / math.sqrt(layers)
            return std * jax.random.normal(key, shape, F32)

        def draw(rng):
            return jax.tree_util.tree_unflatten(treedef, [
                draw_leaf(jax.random.fold_in(rng, i), path, shape)
                for i, (path, shape) in enumerate(leaves)])

        return {"params": jax.jit(draw)(rng)}

    def apply(self, variables, tokens, train: bool = False, mutable=None):
        params = variables["params"]
        rows, length = tokens.shape
        x = params["embed"]["embedding"].at[tokens].get(
            mode="promise_in_bounds").astype(self.dtype)
        counters = jnp.zeros((len(parts.MOE_COUNTER_NAMES),), F32)
        for i, letter in enumerate(self.layer_pattern):
            x, c = run_layer(params[f"layers_{i}"], x, MIXERS[letter], self)
            counters = counters + c
        # Position t is scored against token t + 1. The row keeps its L
        # positions (whole tiles); the last one has weight 0 and the others
        # L / (L - 1), so that `head_loss`'s sum over L is the mean over the
        # L - 1 judged.
        judged = jnp.arange(length) < length - 1
        weight = jnp.broadcast_to(
            jnp.where(judged, length / (length - 1.0), 0.0), tokens.shape)
        loss, hits = parts.rows_head_loss(
            x, params["final_norm"]["scale"], params["head"]["kernel"],
            jnp.roll(tokens, -1, axis=1), weight.astype(F32), self.eps)
        count = jnp.full((rows,), length - 1, jnp.int32)
        counters = jnp.concatenate(
            [counters, jnp.asarray([rows * (length - 1)], F32)])
        return RowLoss(loss=loss, correct=hits, count=count,
                       counters=counters)

"""The plain reference of a block-diffusion mixture-of-experts decoder's
training step (SDAR-30B-A3B-Chat's layer, `configs/sdar-30b-a3b-ep8.json`):
forward, loss, gradients and AdamW in `jax.numpy`, float32, every product at
`jax.default_matmul_precision("highest")`. No code of the program: its own
weights, rows and noise from the seeds, dense attention with the mask
written out, the experts by a plain loop.

The equations (each departure from the published config is in the
configuration file's ``assumed``):

- layer, pre-norm: ``h = x + Attn(RMSNorm(x))``, ``y = h + MoE(RMSNorm(h))``,
  ``RMSNorm(x) = w * x / sqrt(mean(x^2) + eps)``;
- attention: ``q = xWq`` (heads x head_dim), ``k = xWk``, ``v = xWv``
  (kv heads x head_dim), no bias; RMSNorm of ``q`` and ``k`` over head_dim
  with a learned weight; RoPE (rotate-half) by position id; a key/value head
  serves ``heads / kv_heads`` query heads; scores ``/ sqrt(head_dim)``,
  mask, softmax; ``Wo``;
- experts: ``p = softmax(h Wr)`` over all the router's experts, the
  ``top_k`` largest, renormalised to sum 1; ``MoE(h) = sum_e w_e *
  Wdown_e(silu(Wgate_e h) * (Wup_e h))`` over the experts this share holds
  (``held`` of them, from ``share_index * held``): the other terms are left
  out, here as in the program;
- block diffusion (BD3-LM's training form): a row ``x0`` of ``L`` tokens,
  ``t ~ U[t_min, 1]`` a block of ``B`` tokens, each token of the block
  masked with probability ``t`` giving ``xt``; the input is ``[xt ; x0]``,
  both halves at positions ``0..L-1``; a noisy query sees the noisy keys of
  its own block and the clean keys of earlier blocks, a clean query the
  clean keys of its own and earlier blocks; loss ``= 1/(rows*L) * sum`` over
  the masked positions of ``CE(logits_i, x0_i) / t_block``;
- AdamW: bias-corrected, the gradient clipped to a global norm before the
  moments, decoupled decay on every leaf but the norms' weights.
"""

from __future__ import annotations

import functools
import json
import math

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
T_MIN = 0.001


def highest(fn):
    """Every product inside at float32 on any backend."""
    @functools.wraps(fn)
    def wrapped(*args, **kwargs):
        with jax.default_matmul_precision("highest"):
            return fn(*args, **kwargs)
    return wrapped


# ------------------------------------------------------------------ weights

def init_params(model: dict, seed: int) -> dict:
    """The seed's weights: normal(0, init_std) matrices, norm weights 1.
    A leaf's key is the seed's key folded with the leaf's place in the
    (sorted) tree, so a leaf depends on the seed and its own name alone."""
    h, d = int(model["hidden_size"]), int(model["head_dim"])
    nq, nkv = int(model["num_attention_heads"]), int(model["num_key_value_heads"])
    held, width = int(model["num_experts"]), int(model["moe_intermediate_size"])
    vocab, router = int(model["vocab_size"]), int(model["router_experts"])
    shapes = {"embed/embedding": (vocab, h), "final_norm/scale": (h,),
              "head/kernel": (h, vocab)}
    for i in range(int(model["num_hidden_layers"])):
        p = f"layers_{i}/"
        shapes.update({
            p + "attn_norm/scale": (h,), p + "moe_norm/scale": (h,),
            p + "attn/q_proj/kernel": (h, nq * d),
            p + "attn/k_proj/kernel": (h, nkv * d),
            p + "attn/v_proj/kernel": (h, nkv * d),
            p + "attn/o_proj/kernel": (nq * d, h),
            p + "attn/q_norm/scale": (d,), p + "attn/k_norm/scale": (d,),
            p + "moe/router/kernel": (h, router),
            p + "moe/gate/kernel": (held, h, width),
            p + "moe/up/kernel": (held, h, width),
            p + "moe/down/kernel": (held, width, h),
        })
    std = float(model.get("init_std", 0.02))
    root = jax.random.fold_in(jax.random.PRNGKey(int(seed)), 0x77656967)

    @jax.jit
    def make():
        tree = {}
        for place, (path, shape) in enumerate(sorted(shapes.items())):
            if path.endswith("/scale"):
                leaf = jnp.ones(shape, F32)
            else:
                leaf = std * jax.random.normal(
                    jax.random.fold_in(root, place), shape, F32)
            node = tree
            *parents, name = path.split("/")
            for key in parents:
                node = node.setdefault(key, {})
            node[name] = leaf
        return tree

    return make()


# -------------------------------------------------------------------- noise

def draw_noise(seed: int, step, x0, block: int, mask_id: int):
    """``(xt, weight)`` of global step ``step``: the program's draw restated.
    ``PRNGKey(seed)`` folded with the step, split in two: one uniform a
    block for ``t`` in ``[T_MIN, 1]``, one uniform a token; a token is
    masked where its uniform lies under its block's ``t``. ``weight`` is
    ``1/t`` on the masked tokens and 0 elsewhere. ``seed`` is the program's
    ``train.seed + 1``."""
    rows, length = x0.shape
    k_t, k_u = jax.random.split(
        jax.random.fold_in(jax.random.PRNGKey(int(seed)), step))
    t = jax.random.uniform(k_t, (rows, length // block), F32, T_MIN, 1.0)
    t = jnp.repeat(t, block, axis=1)
    masked = jax.random.uniform(k_u, (rows, length), F32) < t
    xt = jnp.where(masked, jnp.int32(mask_id), x0)
    return xt, jnp.where(masked, 1.0 / t, 0.0)


def attention_mask(length: int, block: int, fault: str | None = None):
    """``[2L, 2L]`` bool, query by key, over ``[noisy ; clean]``: the three
    rules written out."""
    if fault == "causal_mask":
        i = np.arange(2 * length)
        return jnp.asarray(i[None, :] <= i[:, None])
    blk = np.arange(length) // block
    q, k = blk[:, None], blk[None, :]
    noisy_noisy = q == k          # a noisy query: the noisy keys of its block
    noisy_clean = k < q           # ... and the clean keys of earlier blocks
    clean_noisy = np.zeros_like(noisy_noisy)  # a clean query: no noisy key
    clean_clean = k <= q          # ... the clean keys up to its own block
    return jnp.asarray(np.block([[noisy_noisy, noisy_clean],
                                 [clean_noisy, clean_clean]]))


# ------------------------------------------------------------------ forward

def _cast(x, dtype):
    """Round to ``dtype`` and carry on in float32 (the lower-precision
    controls: every operand of a product is rounded, the sum is not)."""
    return x if dtype is None else x.astype(dtype).astype(F32)


def rms_norm(x, weight, eps):
    return weight * x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)


def rope(x, positions, theta):
    """Rotate-half form; ``x`` is ``[positions, heads, head_dim]``."""
    half = x.shape[-1] // 2
    freq = 1.0 / (theta ** (jnp.arange(half, dtype=F32) / half))
    angle = positions.astype(F32)[:, None] * freq[None, :]
    cos = jnp.concatenate([jnp.cos(angle)] * 2, -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(angle)] * 2, -1)[:, None, :]
    rotated = jnp.concatenate([-x[..., half:], x[..., :half]], -1)
    return x * cos + rotated * sin


def attention(p, x, positions, mask, model, dtype=None):
    """One row: ``x`` is ``[2L, hidden]``. A query head at a time (a dense
    ``[2L, 2L]`` score array of a head is all that is live)."""
    d = int(model["head_dim"])
    nq, nkv = int(model["num_attention_heads"]), int(model["num_key_value_heads"])
    eps, theta = float(model["rms_norm_eps"]), float(model["rope_theta"])
    n = x.shape[0]
    xc = _cast(x, dtype)
    q = (xc @ _cast(p["q_proj"]["kernel"], dtype)).reshape(n, nq, d)
    k = (xc @ _cast(p["k_proj"]["kernel"], dtype)).reshape(n, nkv, d)
    v = (xc @ _cast(p["v_proj"]["kernel"], dtype)).reshape(n, nkv, d)
    q = rope(rms_norm(q, p["q_norm"]["scale"], eps), positions, theta)
    k = rope(rms_norm(k, p["k_norm"]["scale"], eps), positions, theta)
    q, k, v = _cast(q, dtype), _cast(k, dtype), _cast(v, dtype)

    @jax.checkpoint
    def one_head(qh, kh, vh):
        scores = (qh @ kh.T) / math.sqrt(d)
        scores = jnp.where(mask, scores, -jnp.inf)
        return _cast(jax.nn.softmax(scores, axis=-1), dtype) @ vh

    group = nq // nkv
    heads = jax.lax.map(
        lambda i: one_head(q[:, i], k[:, i // group], v[:, i // group]),
        jnp.arange(nq))                                   # [nq, n, d]
    out = jnp.moveaxis(heads, 0, 1).reshape(n, nq * d)
    return _cast(out, dtype) @ _cast(p["o_proj"]["kernel"], dtype)


def route(p, h, model, fault=None):
    """``(weights [n, top_k], experts [n, top_k])`` over all the router's
    experts, float32 whatever the precision of the rest."""
    probs = jax.nn.softmax(h @ p["router"]["kernel"], axis=-1)
    weights, experts = jax.lax.top_k(probs, int(model["num_experts_per_tok"]))
    if model.get("norm_topk_prob", True) and fault != "no_topk_renorm":
        weights = weights / jnp.sum(weights, -1, keepdims=True)
    return weights, experts


def experts_share(p, h, model, dtype=None, fault=None, held=None,
                  share_index=None):
    """The part of ``MoE(h)`` that this share's experts give, one expert
    after another over every position, kept where the router sent it."""
    held = int(model["num_experts"]) if held is None else held
    first = held * int(model.get("share_index", 0)
                       if share_index is None else share_index)
    weights, experts = route(p, h, model, fault)
    if fault == "capacity_drop":
        # capacity 1.0: an expert takes positions * top_k / experts
        # assignments, in order of position, and drops the rest
        cap = h.shape[0] * experts.shape[1] // int(model["router_experts"])
        onehot = jax.nn.one_hot(experts, int(model["router_experts"]),
                                dtype=jnp.int32)          # [n, k, E]
        flat = onehot.reshape(-1, onehot.shape[-1])
        rank = (jnp.cumsum(flat, 0) - flat).reshape(onehot.shape)
        kept = jnp.sum(onehot * (rank < cap), -1) > 0
        weights = jnp.where(kept, weights, 0.0)
    hc = _cast(h, dtype)

    @jax.checkpoint
    def one_expert(gate, up, down, e):
        w_e = jnp.sum(jnp.where(experts == first + e, weights, 0.0), -1)
        act = _cast(jax.nn.silu(hc @ _cast(gate, dtype))
                    * (hc @ _cast(up, dtype)), dtype)
        return w_e[:, None] * (act @ _cast(down, dtype))

    # a plain loop over the held experts, one after another (rolled, so
    # that the program holds one expert's products and not all of them)
    out, _ = jax.lax.scan(
        lambda total, xs: (total + one_expert(*xs), None), jnp.zeros_like(h),
        (p["gate"]["kernel"][:held], p["up"]["kernel"][:held],
         p["down"]["kernel"][:held], jnp.arange(held)))
    return out


def layer(p, x, positions, mask, model, dtype=None, fault=None):
    eps = float(model["rms_norm_eps"])
    h = x + attention(p["attn"], rms_norm(x, p["attn_norm"]["scale"], eps),
                      positions, mask, model, dtype)
    return h + experts_share(p["moe"], rms_norm(h, p["moe_norm"]["scale"], eps),
                             model, dtype, fault)


def row_loss(params, xt, x0, weight, model, dtype=None, fault=None):
    """One row's ``sum_i weight_i * CE(logits_i, x0_i) / L`` and the number
    of masked positions whose largest logit is the clean token."""
    length = x0.shape[0]
    mask = attention_mask(length, int(model["block_length"]), fault)
    positions = jnp.concatenate([jnp.arange(length)] * 2)
    x = params["embed"]["embedding"][jnp.concatenate([xt, x0])]
    for i in range(int(model["num_hidden_layers"])):
        x = jax.checkpoint(
            functools.partial(layer, model=model, dtype=dtype, fault=fault)
        )(params[f"layers_{i}"], x, positions, mask)
    noisy = rms_norm(x[:length], params["final_norm"]["scale"],
                     float(model["rms_norm_eps"]))
    logits = noisy @ params["head"]["kernel"]
    nll = jax.nn.logsumexp(logits, -1) - jnp.take_along_axis(
        logits, x0[:, None], -1)[:, 0]
    hits = jnp.sum((jnp.argmax(logits, -1) == x0) & (weight > 0))
    return jnp.sum(weight * nll) / length, hits


@functools.lru_cache(maxsize=None)
def _row_grad_fn(model_json: str, dtype, fault):
    """One row's loss, hits and gradient, the gradient added into the
    running mean's buffer (donated: one gradient tree is live, not two).
    Jitted once a (model, precision, fault)."""
    grad = jax.value_and_grad(
        functools.partial(row_loss, model=json.loads(model_json), dtype=dtype,
                          fault=fault), has_aux=True)

    def step(params, total, xt, x0, weight, scale):
        (loss, hits), g = grad(params, xt, x0, weight)
        return loss, hits, jax.tree_util.tree_map(
            lambda a, b: a + b * scale, total, g)

    return jax.jit(step, donate_argnums=(1,))


@highest
def loss_and_grads(params, x0, noise, model, dtype=None, fault=None):
    """Mean over the rows of `row_loss`, its gradient, and the hits; a row
    at a time, so that a row's activations are all that is live."""
    xt, weight = noise
    rows = x0.shape[0]
    step = _row_grad_fn(json.dumps(model, sort_keys=True), dtype, fault)
    loss, hits = 0.0, 0
    grads = jax.tree_util.tree_map(jnp.zeros_like, params)
    for r in range(rows):
        l, n, grads = step(params, grads, xt[r], x0[r], weight[r], 1.0 / rows)
        loss, hits = loss + l / rows, hits + n
    return loss, grads, hits


# -------------------------------------------------------------------- AdamW

def adamw_init(params):
    zeros = jax.tree_util.tree_map(jnp.zeros_like, params)
    return {"count": jnp.zeros((), jnp.int32), "m": zeros,
            "v": jax.tree_util.tree_map(jnp.zeros_like, params)}


@functools.lru_cache(maxsize=None)
def _adamw_fn(opt_json: str):
    opt = json.loads(opt_json)
    b1, b2, eps = float(opt["b1"]), float(opt["b2"]), float(opt["eps"])
    wd, max_norm = float(opt["weight_decay"]), float(opt["clip_norm"])

    def update(params, grads, state, lr):
        norm = jnp.sqrt(sum(jnp.sum(g * g)
                            for g in jax.tree_util.tree_leaves(grads)))
        scale = jnp.minimum(1.0, max_norm / (norm + 1e-6))
        grads = jax.tree_util.tree_map(lambda g: g * scale, grads)
        count = state["count"] + 1
        c1 = 1.0 - b1 ** count.astype(F32)
        c2 = 1.0 - b2 ** count.astype(F32)
        m = jax.tree_util.tree_map(lambda m, g: b1 * m + (1 - b1) * g,
                                   state["m"], grads)
        v = jax.tree_util.tree_map(lambda v, g: b2 * v + (1 - b2) * g * g,
                                   state["v"], grads)

        def leaf(path, p, m, v):
            step = (m / c1) / (jnp.sqrt(v / c2) + eps)
            name = str(getattr(path[-1], "key", path[-1]))
            decay = 0.0 if name == "scale" else wd
            return p - lr * (step + decay * p)

        new = jax.tree_util.tree_map_with_path(leaf, params, m, v)
        return new, {"count": count, "m": m, "v": v}, grads

    # every tree is updated in place: parameters, moments, and the
    # gradient, which leaves as the clipped gradient
    return jax.jit(update, donate_argnums=(0, 1, 2))


def adamw_update(params, grads, state, lr, opt: dict):
    """One step: ``(parameters, state, clipped gradient)``. The gradient is
    clipped to ``clip_norm`` first; the decay is decoupled (``p -= lr * wd
    * p``) and spares the norms' weights. The arguments' buffers are reused
    for the results."""
    return _adamw_fn(json.dumps(opt, sort_keys=True))(params, grads, state, lr)


def follow(model: dict, opt: dict, make_params, batches, noise_seed: int,
           dtype=None, fault: str | None = None, leaf_norms=None) -> dict:
    """Follow ``len(batches)`` steps from ``make_params()``: each step's
    loss and hits, the final parameters and, with ``leaf_norms``, the first
    (clipped) gradient's norms and the norms of the parameters' change, by
    leaf. ``batches`` are the rows ``x0`` of each global step; the learning
    rate is constant (``opt["lr"]``, no warm-up). The starting parameters
    are made a second time for the change, so that one copy of them is live
    while the steps run."""
    block, mask_id = int(model["block_length"]), int(model["mask_token_id"])
    params = make_params()
    state = adamw_init(params)
    losses, hits, grad1 = [], [], None
    for k, x0 in enumerate(batches):
        if fault == "half_batch":
            x0 = x0[:x0.shape[0] // 2]
        noise = draw_noise(noise_seed, k, x0, block, mask_id)
        loss, grads, n = loss_and_grads(params, x0, noise, model, dtype, fault)
        params, state, clipped = adamw_update(params, grads, state,
                                              float(opt["lr"]), opt)
        losses.append(float(loss))
        hits.append(int(n))
        if k == 0 and leaf_norms is not None:
            grad1 = {k: float(v) for k, v in leaf_norms(clipped).items()}
        del grads, clipped
    out = {"loss": losses, "hits": hits, "params": params}
    if leaf_norms is not None:
        del state
        delta = leaf_norms(jax.tree_util.tree_map(
            lambda a, b: a - b, params, make_params()))
        out["grad1"] = grad1
        out["delta"] = {k: float(v) for k, v in delta.items()}
    return out

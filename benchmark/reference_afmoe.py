"""The plain reference of the training step of a decoder of sliding-window and
full attention layers with experts (the `afmoe` layers of Trinity-Mini,
`configs/trinity-mini-ep8.json`): forward, next-token loss, gradients and
AdamW in `jax.numpy`, float32, every product at
`jax.default_matmul_precision("highest")`. No code of the program: its own
weights and rows from the seeds, attention a head at a time in blocks of
queries over the keys each block sees, the experts by a plain loop. AdamW
and three helpers are `reference_nemotron_h.py`'s (the same optimizer, the
same constants).

The equations (each part the published config does not show is in the
configuration file's ``assumed``). ``RMSNorm(x; w) = w * x / sqrt(mean(x^2)
+ eps)``; ``x = E[ids] * sqrt(hidden)``; layer ``l`` is

- ``a = RMSNorm(x)``; ``q = a W_q`` (heads x head_dim), ``k, v = a W_k, a
  W_v`` (kv heads x head_dim), ``g = a W_g`` (heads x head_dim); ``q, k`` a
  per-head RMSNorm each; on a ``sliding_attention`` layer rotate-half RoPE
  of ``q, k`` over all of a head's dims at ``rope_theta``, on a
  ``full_attention`` layer no positional encoding; ``o_h = softmax(q_h
  k_{h // (heads / kv)}^T / sqrt(head_dim) + M) v_{h // (heads / kv)}``,
  ``M`` causal (``k <= q``) and on a sliding layer also ``q - k <
  sliding_window``; ``x <- x + RMSNorm(((concat_h o_h) * sigmoid(g)) W_o)``;
- ``b = RMSNorm(x)``; the first ``num_dense_layers`` layers ``m = (silu(b
  W_1) * (b W_3)) W_2``; the others ``s = sigmoid(b W_r)`` over all the
  router's experts, the chosen the ``top_k`` of ``s + beta``, their weights
  ``route_scale * s_e / sum_chosen s`` (``route_norm``), ``m = sum_e w_e
  SwiGLU_e(b)`` over the chosen experts this share holds (``held`` of them,
  from ``share_index * held``; the other terms are left out, here as in the
  program) ``+ SwiGLU_shared(b)``; ``x <- x + RMSNorm(m)``;
- head and loss: ``logits = RMSNorm(x; w_f) W_head``; a row's loss is the
  mean over ``t = 0..L-2`` of ``CE(logits_t, token_{t+1})``, a step's the
  mean over its rows;
- AdamW: bias-corrected, the gradient clipped to a global norm before the
  moments, decoupled decay on the matrices only (norms' weights and the
  router's bias spared).
"""

from __future__ import annotations

import functools
import json
import math

import jax
import jax.numpy as jnp
from reference_nemotron_h import _cast, adamw_init, adamw_update, highest

F32 = jnp.float32
QUERY_BLOCK = 2048   # queries a block of attention
HEAD_BLOCK = 4096    # positions a block of head and loss


def sizes(model: dict) -> dict:
    """The shapes' names used below, from the published config's keys."""
    return {
        "hidden": int(model["hidden_size"]),
        "nq": int(model["num_attention_heads"]),
        "nkv": int(model["num_key_value_heads"]), "d": int(model["head_dim"]),
        "dense": int(model["intermediate_size"]),
        "width": int(model["moe_intermediate_size"]),
        "shared": int(model["moe_intermediate_size"])
        * int(model["num_shared_experts"]),
        "held": int(model["num_experts"]),
        "router": int(model["router_experts"]),
        "top_k": int(model["num_experts_per_tok"]),
        "vocab": int(model["vocab_size"]),
        "types": list(model["layer_types"]),
        "dense_layers": int(model["num_dense_layers"]),
        "window": int(model["sliding_window"]),
        "eps": float(model["rms_norm_eps"]),
    }


# ------------------------------------------------------------------ weights

def leaf_shapes(model: dict) -> dict:
    """``{path: shape}`` of every parameter, paths joined with '/'."""
    s = sizes(model)
    h, qd, kvd = s["hidden"], s["nq"] * s["d"], s["nkv"] * s["d"]
    shapes = {"embed/embedding": (s["vocab"], h), "final_norm/scale": (h,),
              "head/kernel": (h, s["vocab"])}
    for i, kind in enumerate(s["types"]):
        if kind not in ("sliding_attention", "full_attention"):
            raise ValueError(f"layer {i}: unknown type {kind!r}")
        p = f"layers_{i}/"
        for norm in ("attn_norm", "post_attn_norm", "mlp_norm",
                     "post_mlp_norm"):
            shapes[p + norm + "/scale"] = (h,)
        shapes.update({
            p + "attn/q_proj/kernel": (h, qd),
            p + "attn/k_proj/kernel": (h, kvd),
            p + "attn/v_proj/kernel": (h, kvd),
            p + "attn/gate_proj/kernel": (h, qd),
            p + "attn/o_proj/kernel": (qd, h),
            p + "attn/q_norm/scale": (s["d"],),
            p + "attn/k_norm/scale": (s["d"],)})
        if i < s["dense_layers"]:
            shapes.update({p + "mlp/gate/kernel": (h, s["dense"]),
                           p + "mlp/up/kernel": (h, s["dense"]),
                           p + "mlp/down/kernel": (s["dense"], h)})
        else:
            shapes.update({
                p + "moe/router/kernel": (h, s["router"]),
                p + "moe/router/bias": (s["router"],),
                p + "moe/gate/kernel": (s["held"], h, s["width"]),
                p + "moe/up/kernel": (s["held"], h, s["width"]),
                p + "moe/down/kernel": (s["held"], s["width"], h),
                p + "moe/shared_gate/kernel": (h, s["shared"]),
                p + "moe/shared_up/kernel": (h, s["shared"]),
                p + "moe/shared_down/kernel": (s["shared"], h)})
    return shapes


def init_params(model: dict, seed: int) -> dict:
    """The seed's weights (the configuration's ``assumed.init``): matrices
    normal(0, init_std), norm weights 1, the router's bias 0. A leaf's key
    is the seed's key folded with the leaf's place in the (sorted) tree."""
    shapes = leaf_shapes(model)
    std = float(model.get("init_std", 0.02))
    root = jax.random.fold_in(jax.random.PRNGKey(int(seed)), 0x77656967)

    def draw(key, path, shape):
        name = path.split("/")[-1]
        if name == "scale":
            return jnp.ones(shape, F32)
        if name == "bias":
            return jnp.zeros(shape, F32)
        return std * jax.random.normal(key, shape, F32)

    @jax.jit
    def make():
        tree = {}
        for place, (path, shape) in enumerate(sorted(shapes.items())):
            node = tree
            *parents, name = path.split("/")
            for key in parents:
                node = node.setdefault(key, {})
            node[name] = draw(jax.random.fold_in(root, place), path, shape)
        return tree

    return make()


# ------------------------------------------------------------------ forward

def rms_norm(x, weight, eps):
    return weight * x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)


def rotate(x, theta):
    """Rotate-half RoPE of ``x [L, heads, d]`` at positions ``0..L-1``:
    ``[x1 cos - x2 sin, x2 cos + x1 sin]`` at ``t * theta^(-2i/d)``."""
    n, d = x.shape[0], x.shape[-1]
    angle = (jnp.arange(n, dtype=F32)[:, None]
             * theta ** (-jnp.arange(d // 2, dtype=F32) / (d // 2)))
    cos, sin = jnp.cos(angle)[:, None], jnp.sin(angle)[:, None]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(p, u, sliding, model, dtype=None, fault=None):
    """One row: ``u [L, hidden]``. A query head at a time, its queries a
    block of `QUERY_BLOCK` at a time against the keys the block sees (the
    whole score matrix of a head at 16,384 positions would be 1 GB)."""
    s = sizes(model)
    n, nq, nkv, d = u.shape[0], s["nq"], s["nkv"], s["d"]
    eps, uc = s["eps"], _cast(u, dtype)
    q, k, v = (
        (uc @ _cast(p[name]["kernel"], dtype)).reshape(n, heads, d)
        for name, heads in (("q_proj", nq), ("k_proj", nkv), ("v_proj", nkv)))
    q = rms_norm(q, p["q_norm"]["scale"], eps)
    k = rms_norm(k, p["k_norm"]["scale"], eps)
    if sliding or fault == "rope_on_full":
        theta = float(model["rope_theta"])
        q, k = rotate(q, theta), rotate(k, theta)
    q, k, v = _cast(q, dtype), _cast(k, dtype), _cast(v, dtype)
    window = s["window"] if sliding and fault != "no_window" else None
    scale = 1.0 / math.sqrt(d)

    @jax.checkpoint
    def one_head(qh, kh, vh):
        out = []
        for first in range(0, n, QUERY_BLOCK):
            last = min(first + QUERY_BLOCK, n)
            lo = 0 if window is None else max(0, first - window + 1)
            at_q = jnp.arange(first, last)[:, None]
            at_k = jnp.arange(lo, last)[None, :]
            seen = at_k <= at_q
            if window is not None:
                seen = seen & (at_q - at_k < window)
            scores = jnp.where(seen, (qh[first:last] @ kh[lo:last].T) * scale,
                               -jnp.inf)
            out.append(_cast(jax.nn.softmax(scores, axis=-1), dtype)
                       @ vh[lo:last])
        return jnp.concatenate(out)

    group = nq // nkv
    heads = jax.lax.map(
        lambda i: one_head(q[:, i], k[:, i // group], v[:, i // group]),
        jnp.arange(nq))                                   # [nq, n, d]
    out = jnp.moveaxis(heads, 0, 1).reshape(n, nq * d)
    if fault != "no_gate":
        out = out * jax.nn.sigmoid(uc @ _cast(p["gate_proj"]["kernel"], dtype))
    return _cast(out, dtype) @ _cast(p["o_proj"]["kernel"], dtype)


def swiglu(u, gate, up, down, dtype=None):
    uc = _cast(u, dtype)
    act = jax.nn.silu(uc @ _cast(gate, dtype)) * (uc @ _cast(up, dtype))
    return _cast(act, dtype) @ _cast(down, dtype)


def route(p, u, model, fault=None):
    """``(weights [n, top_k], experts [n, top_k])`` over all the router's
    experts, float32 whatever the precision of the rest."""
    top_k = int(model["num_experts_per_tok"])
    logits = u @ p["router"]["kernel"]
    if fault == "softmax_router":
        weights, experts = jax.lax.top_k(jax.nn.softmax(logits, -1), top_k)
    else:
        scores = jax.nn.sigmoid(logits)
        _, experts = jax.lax.top_k(scores + p["router"]["bias"], top_k)
        weights = jnp.take_along_axis(scores, experts, axis=-1)
    if model.get("route_norm", True):
        weights = weights / (jnp.sum(weights, -1, keepdims=True) + 1e-20)
    return weights * float(model["route_scale"]), experts


def routed_share(p, u, model, dtype=None, fault=None, held=None,
                 share_index=None):
    """The part of the routed experts' sum that this share's experts give,
    one expert after another over every position, kept where the router
    sent it."""
    held = int(model["num_experts"]) if held is None else held
    first = held * int(model.get("share_index", 0)
                       if share_index is None else share_index)
    weights, experts = route(p, u, model, fault)

    @jax.checkpoint
    def one_expert(gate, up, down, e):
        w_e = jnp.sum(jnp.where(experts == first + e, weights, 0.0), -1)
        return w_e[:, None] * swiglu(u, gate, up, down, dtype)

    out, _ = jax.lax.scan(
        lambda total, xs: (total + one_expert(*xs), None), jnp.zeros_like(u),
        (p["gate"]["kernel"][:held], p["up"]["kernel"][:held],
         p["down"]["kernel"][:held], jnp.arange(held)))
    return out


def shared_expert(p, u, dtype=None):
    return swiglu(u, p["shared_gate"]["kernel"], p["shared_up"]["kernel"],
                  p["shared_down"]["kernel"], dtype)


def moe(p, u, model, dtype=None, fault=None):
    out = routed_share(p, u, model, dtype, fault)
    if fault != "no_shared_expert":
        out = out + shared_expert(p, u, dtype)
    return out


def layer(p, x, i, model, dtype=None, fault=None):
    s = sizes(model)
    eps = s["eps"]
    sliding = s["types"][i] == "sliding_attention"
    a = attention(p["attn"], rms_norm(x, p["attn_norm"]["scale"], eps),
                  sliding, model, dtype, fault)
    x = x + rms_norm(a, p["post_attn_norm"]["scale"], eps)
    b = rms_norm(x, p["mlp_norm"]["scale"], eps)
    if i < s["dense_layers"]:
        f = p["mlp"]
        m = swiglu(b, f["gate"]["kernel"], f["up"]["kernel"],
                   f["down"]["kernel"], dtype)
    else:
        m = moe(p["moe"], b, model, dtype, fault)
    return x + rms_norm(m, p["post_mlp_norm"]["scale"], eps)


def row_loss(params, tokens, model, dtype=None, fault=None):
    """One row's mean over ``t = 0..L-2`` of ``CE(logits_t, token_{t+1})``
    and the number of those positions whose largest logit is the next
    token. Head and loss a block of `HEAD_BLOCK` positions at a time."""
    s = sizes(model)
    n = tokens.shape[0]
    x = params["embed"]["embedding"][tokens] * math.sqrt(s["hidden"])
    for i in range(len(s["types"])):
        x = jax.checkpoint(functools.partial(
            layer, i=i, model=model, dtype=dtype, fault=fault)
        )(params[f"layers_{i}"], x)
    x = rms_norm(x, params["final_norm"]["scale"], s["eps"])
    target = jnp.roll(tokens, -1)
    judged = jnp.arange(n) < n - 1
    block = HEAD_BLOCK if n % HEAD_BLOCK == 0 else n

    @jax.checkpoint
    def piece(xs):
        x, target, judged = xs
        logits = x @ params["head"]["kernel"]
        nll = jax.nn.logsumexp(logits, -1) - jnp.take_along_axis(
            logits, target[:, None], -1)[:, 0]
        return (jnp.sum(jnp.where(judged, nll, 0.0)),
                jnp.sum((jnp.argmax(logits, -1) == target) & judged))

    nll, hits = jax.lax.map(piece, tuple(
        t.reshape(n // block, block, *t.shape[1:])
        for t in (x, target, judged)))
    return jnp.sum(nll) / (n - 1), jnp.sum(hits)


@functools.lru_cache(maxsize=None)
def _row_grad_fn(model_json: str, dtype, fault):
    """One row's loss, hits and gradient, the gradient added into the
    running mean's buffer (donated: one gradient tree is live beside the
    row's own, not a third). Jitted once a (model, precision, fault)."""
    grad = jax.value_and_grad(
        functools.partial(row_loss, model=json.loads(model_json), dtype=dtype,
                          fault=fault), has_aux=True)

    def step(params, total, tokens, scale):
        (loss, hits), g = grad(params, tokens)
        return loss, hits, jax.tree_util.tree_map(
            lambda a, b: a + b * scale, total, g)

    return jax.jit(step, donate_argnums=(1,))


@highest
def loss_and_grads(params, tokens, model, dtype=None, fault=None):
    """Mean over the rows of `row_loss`, its gradient, and the hits; a row
    at a time, so that a row's activations are all that is live."""
    rows = tokens.shape[0]
    step = _row_grad_fn(json.dumps(model, sort_keys=True), dtype, fault)
    loss, hits = 0.0, 0
    grads = jax.tree_util.tree_map(jnp.zeros_like, params)
    for r in range(rows):
        l, n, grads = step(params, grads, tokens[r], 1.0 / rows)
        loss, hits = loss + l / rows, hits + n
    return loss, grads, hits


def follow(model: dict, opt: dict, make_params, batches, dtype=None,
           fault: str | None = None, leaf_norms=None) -> dict:
    """Follow ``len(batches)`` steps from ``make_params()``: each step's
    loss and hits, the final parameters and, with ``leaf_norms``, the first
    (clipped) gradient's norms and the norms of the parameters' change, by
    leaf. The learning rate is constant (``opt["lr"]``, no warm-up). AdamW's
    moments wait on the host while a gradient is computed (parameters, the
    running gradient and a row's own gradient and activations fill the
    chip), and the starting parameters are made a second time for the
    change."""
    params = make_params()
    state = jax.device_get(adamw_init(params))
    losses, hits, grad1 = [], [], None
    for k, tokens in enumerate(batches):
        loss, grads, n = loss_and_grads(params, tokens, model, dtype, fault)
        params, state, clipped = adamw_update(
            params, grads, jax.device_put(state), float(opt["lr"]), opt)
        state = jax.device_get(state)
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

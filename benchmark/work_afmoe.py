"""Required work of one trained token of a decoder of sliding-window and full
attention layers with experts (`afmoe`), reckoned from the configuration's
shapes alone (never from what implements it), as `work_nemotron_h.py` does
for the hybrid decoder.

An item is a token. For a token, forward, 2 x MACs:

- attention, every layer: projections ``hidden x (2 heads + 2 kv) x
  head_dim`` in (q, k, v and the output gate), ``heads x head_dim x
  hidden`` out; scores and values over the keys the query sees, at ``2 x 2
  x head_dim`` a pair and query head: on a ``sliding_attention`` layer the
  last ``sliding_window`` keys up to the query (fewer near the row's start;
  1,920.06 on average at 16,384 positions and a window of 2,048), on a
  ``full_attention`` layer every key up to it, ``(L + 1) / 2`` on average;
- the first ``num_dense_layers`` layers' feed-forward, three products of
  ``hidden x intermediate_size``;
- the others' experts: the router ``hidden x router_experts``; the shared
  expert, three products of ``hidden x moe_intermediate_size``; of the
  routed pairs the share even routing gives this chip, ``top_k x held /
  router_experts`` terms of three products of ``hidden x
  moe_intermediate_size``.

The head, ``hidden x vocabulary``, once a token. Backward is twice forward;
the embedding is a gather. Norms, the gate's sigmoid, RoPE, softmax, the
sort and the gathers of routing count nothing, and recomputation is not
required work.

The least time of a step: the FLOPs over the peak, then the optimizer's
pass over the state (AdamW reads gradient, parameter and both moments and
writes the last three: 28 bytes a parameter), which waits for the whole
gradient's norm and so overlaps no product. A lower bound by construction.
`window_attention_least_seconds` is kept here whatever implements it.
"""

from __future__ import annotations


def _shape(config: dict) -> dict:
    return {
        "h": int(config["hidden_size"]), "d": int(config["head_dim"]),
        "nq": int(config["num_attention_heads"]),
        "nkv": int(config["num_key_value_heads"]),
        "dense": int(config["intermediate_size"]),
        "width": int(config["moe_intermediate_size"]),
        "shared": int(config["moe_intermediate_size"])
        * int(config["num_shared_experts"]),
        "held": int(config["num_experts"]),
        "router": int(config["router_experts"]),
        "top_k": int(config["num_experts_per_tok"]),
        "vocab": int(config["vocab_size"]),
        "types": list(config["layer_types"]),
        "dense_layers": int(config["num_dense_layers"]),
        "window": int(config["sliding_window"]),
    }


def window_keys(length: int, window: int) -> float:
    """The keys a query of a row of ``length`` sees on average in a window
    of ``window``, its own among them."""
    near = min(window, length)
    return (near * (near + 1) / 2 + (length - near) * near) / length


def forward_flops_per_token(config: dict, length: int) -> dict:
    """FLOPs of one token, forward, by part: ``{"attention": {...},
    "dense": float, "experts": {...}, "head": float}``; of attention the
    projections and the scores and values of a sliding and of a full
    layer."""
    s = _shape(config)
    h, d, nq = s["h"], s["d"], s["nq"]
    pair = 2.0 * 2.0 * d * nq
    return {
        "attention": {
            "projections": 2.0 * h * (2 * nq + 2 * s["nkv"]) * d
            + 2.0 * nq * d * h,
            "sliding_scores_and_values": window_keys(length, s["window"])
            * pair,
            "full_scores_and_values": (length + 1) / 2.0 * pair,
        },
        "dense": 2.0 * 3.0 * h * s["dense"],
        "experts": {
            "shared_expert": 2.0 * 3.0 * h * s["shared"],
            "router": 2.0 * h * s["router"],
            "routed_experts": s["top_k"] * s["held"] / s["router"]
            * 2.0 * 3.0 * h * s["width"],
        },
        "head": 2.0 * h * s["vocab"],
    }


def parameters(config: dict) -> int:
    s = _shape(config)
    h, d, nq, nkv = s["h"], s["d"], s["nq"], s["nkv"]
    attention = (h * (2 * nq + 2 * nkv) * d + nq * d * h + 2 * d + 4 * h)
    dense = 3 * h * s["dense"]
    experts = (s["held"] * 3 * h * s["width"] + 3 * h * s["shared"]
               + h * s["router"] + s["router"])
    layers = len(s["types"])
    return (layers * attention + s["dense_layers"] * dense
            + (layers - s["dense_layers"]) * experts
            + 2 * s["vocab"] * h + h)


def train_flops_per_item(config: dict, length: int) -> float:
    """Forward and backward, one token of a row of ``length``."""
    s = _shape(config)
    part = forward_flops_per_token(config, length)
    attn = part["attention"]
    sliding = s["types"].count("sliding_attention")
    layers = len(s["types"])
    forward = (layers * attn["projections"]
               + sliding * attn["sliding_scores_and_values"]
               + (layers - sliding) * attn["full_scores_and_values"]
               + s["dense_layers"] * part["dense"]
               + (layers - s["dense_layers"]) * sum(part["experts"].values())
               + part["head"])
    return 3.0 * forward


def least_step_seconds(config: dict, length: int, rows: int,
                       flops_per_s: float, bytes_per_s: float) -> dict:
    """The least time of a step of ``rows`` rows on a chip with these
    peaks, and its two parts."""
    compute = (train_flops_per_item(config, length) * length * rows
               / flops_per_s)
    update = 28.0 * parameters(config) / bytes_per_s
    return {"seconds": compute + update, "compute_bound_seconds": compute,
            "bandwidth_bound_seconds": update}


def window_attention_least_seconds(config: dict, length: int, rows: int,
                                   peaks: dict) -> float:
    """The least time of a step's sliding-window attention alone: the
    scores and values of every sliding layer over the pairs its window
    leaves, forward and twice that backward, at the peak. Its operands (q,
    k, v, the output and their gradients, 2 bytes an element) would take a
    small share of that to move, so the products bound it."""
    part = forward_flops_per_token(config, length)["attention"]
    sliding = list(config["layer_types"]).count("sliding_attention")
    flops = 3.0 * part["sliding_scores_and_values"] * length * rows * sliding
    return flops / peaks["bf16_flops_per_s"]

"""Required work of one trained token of a block-diffusion mixture-of-experts
decoder, reckoned from the configuration's shapes alone (never from what
implements it), as `work.py` does for the ResNets.

An item is a clean token. Its row enters the model twice, as ``[xt ; x0]``,
so an item is two positions in every layer. For a position and a layer,
forward, 2 x MACs:

- projections: ``hidden x (heads + 2 kv_heads) x head_dim`` in, ``heads x
  head_dim x hidden`` out;
- scores and values over the *unmasked* pairs only: a noisy query of block
  ``b`` sees its own block and the ``b`` clean blocks before it, a clean
  query its own and the ``b`` before it, so a position sees ``B * (nb + 1)
  / 2`` keys on average (``nb = L / B`` blocks), at ``2 x 2 x head_dim`` a
  pair and query head;
- the router, ``hidden x experts``;
- the expert terms that even routing gives this share: ``top_k * held /
  experts`` of them (one at 8 x 16 / 128), each three products of ``hidden x
  width``.

The head, ``hidden x vocabulary``, is on the noisy half only: once an item.
Backward is twice forward (input and weight gradients); the embedding is a
gather. Norms, RoPE, softmax, SiLU, the sort and the gathers of routing
count nothing, and recomputation is not required work.

The least time of a step: the FLOPs over the peak, then the optimizer's
pass over the state (AdamW reads gradient, parameter and both moments and
writes the last three: 28 bytes a parameter), which waits for the whole
gradient's norm and so overlaps no product. A lower bound by construction.
"""

from __future__ import annotations


def _shape(config: dict) -> dict:
    return {k: int(config[k]) for k in (
        "hidden_size", "num_hidden_layers", "num_attention_heads",
        "num_key_value_heads", "head_dim", "moe_intermediate_size",
        "router_experts", "num_experts", "num_experts_per_tok", "vocab_size",
        "block_length")}


def forward_flops_per_position_layer(config: dict, length: int) -> dict:
    """FLOPs of one position in one layer, forward, by part."""
    s = _shape(config)
    h, d = s["hidden_size"], s["head_dim"]
    nq, nkv = s["num_attention_heads"], s["num_key_value_heads"]
    blocks = length // s["block_length"]
    keys_seen = s["block_length"] * (blocks + 1) / 2.0
    terms = s["num_experts_per_tok"] * s["num_experts"] / s["router_experts"]
    return {
        "projections": 2.0 * h * (nq + 2 * nkv) * d + 2.0 * nq * d * h,
        "scores_and_values": keys_seen * 2.0 * 2.0 * d * nq,
        "router": 2.0 * h * s["router_experts"],
        "experts": terms * 3.0 * 2.0 * h * s["moe_intermediate_size"],
    }


def parameters(config: dict) -> int:
    s = _shape(config)
    h, d = s["hidden_size"], s["head_dim"]
    nq, nkv = s["num_attention_heads"], s["num_key_value_heads"]
    layer = (h * (nq + 2 * nkv) * d + nq * d * h + h * s["router_experts"]
             + 2 * h + 2 * d
             + s["num_experts"] * 3 * h * s["moe_intermediate_size"])
    return s["num_hidden_layers"] * layer + 2 * s["vocab_size"] * h + h


def train_flops_per_item(config: dict, length: int) -> float:
    """Forward and backward, one clean token of a row of ``length``."""
    s = _shape(config)
    layer = sum(forward_flops_per_position_layer(config, length).values())
    head = 2.0 * s["hidden_size"] * s["vocab_size"]
    return 3.0 * (2.0 * s["num_hidden_layers"] * layer + head)


def least_step_seconds(config: dict, length: int, rows: int,
                       flops_per_s: float, bytes_per_s: float) -> dict:
    """The least time of a step of ``rows`` rows on a chip with these
    peaks, and its two parts."""
    compute = train_flops_per_item(config, length) * length * rows / flops_per_s
    update = 28.0 * parameters(config) / bytes_per_s
    return {"seconds": compute + update, "compute_bound_seconds": compute,
            "bandwidth_bound_seconds": update}


def attention_least_seconds(config: dict, length: int, rows: int,
                            peaks: dict) -> float:
    """The least time of a step's attention alone: the scores and values
    of every layer over the unmasked pairs, forward and twice that
    backward, at the peak. Its operands (q, k, v, the output and their
    gradients, 2 bytes an element) would take a twentieth of that to move,
    so the products bound it."""
    s = _shape(config)
    part = forward_flops_per_position_layer(config, length)
    flops = (3.0 * part["scores_and_values"] * 2.0 * length * rows
             * s["num_hidden_layers"])
    return flops / peaks["bf16_flops_per_s"]

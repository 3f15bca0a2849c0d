"""Required work of one trained item, reckoned from the configuration's shapes.

The numbers here depend on the configuration's file alone, never on what
implements it: for every convolution and the dense head, 2 x MACs forward
and twice that backward (input gradient and weight gradient), with no input
gradient for the stem, whose input is the data. Elementwise passes (batch
norm, ReLU, the residual add, the pooling) count nothing: they can be fused
into the contractions, so a lower bound leaves them out. Recomputed work
does not count either.

For the roofline, each of the three contractions of a layer (forward, input
gradient, weight gradient) moves its two operands and its result once, at
the configured compute width; its least time on a chip is the larger of
FLOPs over the peak and bytes over the bandwidth, and the step's least time
is the sum over contractions. That is a lower bound by construction, so a
share of it cannot honestly read over 100%.
"""

from __future__ import annotations

import dataclasses
import json
from pathlib import Path

_DTYPE_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4, "float8": 1}


@dataclasses.dataclass(frozen=True)
class Contraction:
    """One convolution (a dense layer is a 1x1 convolution on a 1x1 map)."""

    name: str
    in_hw: int       # input height = width
    out_hw: int      # output height = width
    kernel: int      # kernel height = width
    cin: int
    cout: int
    input_grad: bool = True  # False for the stem: its input is the data

    @property
    def macs(self) -> int:
        """Multiply-accumulates of the forward pass, one item."""
        return self.out_hw * self.out_hw * self.kernel * self.kernel \
            * self.cin * self.cout

    @property
    def weights(self) -> int:
        return self.kernel * self.kernel * self.cin * self.cout

    def passes(self, batch: int, width: int) -> list[tuple[str, float, float]]:
        """(name, FLOPs, bytes) of each contraction this layer needs for
        ``batch`` items at ``width`` bytes an element."""
        flops = 2.0 * self.macs * batch
        x = self.in_hw * self.in_hw * self.cin * batch * width
        y = self.out_hw * self.out_hw * self.cout * batch * width
        w = self.weights * width
        out = [(f"{self.name}.fwd", flops, x + w + y),
               (f"{self.name}.wgrad", flops, x + y + w)]
        if self.input_grad:
            out.append((f"{self.name}.xgrad", flops, y + w + x))
        return out


def contractions(model: dict) -> list[Contraction]:
    """Every contraction of the configuration's model, in forward order.

    ``model`` is the ``model`` object of a configuration's file: a residual
    network of ``basic`` (two 3x3) or ``bottleneck`` (1x1, 3x3, 1x1 with a
    fourfold expansion) blocks, a 3x3 stride-1 stem, stride 2 at the first
    block of every stage but the first, a 1x1 projection on the shortcut
    wherever the shape changes, global average pooling and a dense head.
    """
    block = model["block"]
    if block not in ("basic", "bottleneck"):
        raise ValueError(f"unknown block kind {block!r}")
    hw = int(model["image_size"])
    cin = int(model["image_channels"])
    stem = int(model["stem_filters"])
    out = [Contraction("stem", hw, hw, 3, cin, stem, input_grad=False)]
    cin = stem
    n = 0
    for stage, (filters, count) in enumerate(
            zip(model["stage_filters"], model["stage_blocks"])):
        for j in range(int(count)):
            stride = 2 if stage > 0 and j == 0 else 1
            ohw = hw // stride
            tag = f"block{n}"
            if block == "basic":
                cout = int(filters)
                out.append(Contraction(f"{tag}.conv0", hw, ohw, 3, cin, cout))
                out.append(Contraction(f"{tag}.conv1", ohw, ohw, 3, cout, cout))
            else:
                mid, cout = int(filters), 4 * int(filters)
                out.append(Contraction(f"{tag}.conv0", hw, hw, 1, cin, mid))
                out.append(Contraction(f"{tag}.conv1", hw, ohw, 3, mid, mid))
                out.append(Contraction(f"{tag}.conv2", ohw, ohw, 1, mid, cout))
            if stride != 1 or cin != cout:
                out.append(Contraction(f"{tag}.shortcut", hw, ohw, 1, cin, cout))
            hw, cin = ohw, cout
            n += 1
    out.append(Contraction("head", 1, 1, 1, cin, int(model["num_classes"])))
    return out


def macs_per_item(model: dict) -> int:
    """Forward multiply-accumulates of one item."""
    return sum(c.macs for c in contractions(model))


def parameters(model: dict) -> int:
    """Trainable parameters: contraction weights, the head's bias, and a
    scale and a bias for the batch norm after every convolution."""
    cs = contractions(model)
    convs = [c for c in cs if c.name != "head"]
    head = cs[-1]
    return (sum(c.weights for c in cs) + head.cout
            + sum(2 * c.cout for c in convs))


def train_flops_per_item(model: dict) -> float:
    """Required FLOPs to train on one item: forward plus backward."""
    total = 0.0
    for c in contractions(model):
        total += 2.0 * c.macs * (3 if c.input_grad else 2)
    return total


def least_step_seconds(model: dict, batch: int, compute_dtype: str,
                       peak_flops: float, peak_bytes: float) -> dict:
    """The least time one chip could take for a step on ``batch`` items.

    Returns the seconds, and how much of them the bandwidth bounds, so that
    a reader can say which side of the roofline the step stands on.
    """
    width = _DTYPE_BYTES[compute_dtype]
    seconds = by_bytes = 0.0
    for c in contractions(model):
        for _, flops, nbytes in c.passes(batch, width):
            t_f, t_b = flops / peak_flops, nbytes / peak_bytes
            seconds += max(t_f, t_b)
            if t_b > t_f:
                by_bytes += t_b
    return {"seconds": seconds, "bandwidth_bound_seconds": by_bytes}


def load_peaks(device_kind: str, path: Path | None = None) -> dict:
    """The chip's published peaks; an unknown ``device_kind`` is an error."""
    path = path or Path(__file__).resolve().parent / "peaks.json"
    table = json.loads(path.read_text())["chips"]
    if device_kind not in table:
        raise KeyError(
            f"device kind {device_kind!r} is not in {path.name}: add its "
            f"published peaks with their source before measuring on it")
    return table[device_kind]

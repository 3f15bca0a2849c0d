"""Model zoo: the reference's `Net` (behavioral parity) and CIFAR ResNets.

The reference defines one model, a LeNet-style CNN
(`/root/reference/cifar_example.py:17-34`), which cannot reach the 93% top-1
north-star; BASELINE.json's configs name ResNet-18/50, so the zoo carries
both (SURVEY.md §6 note).
"""

from tpu_dp.models.net import Net
from tpu_dp.models.outputs import RowLoss
from tpu_dp.models.resnet import ResNet, ResNet18, ResNet50
from tpu_dp.models.sdar import BlockDiffusionMoE

_REGISTRY = {
    "sdar_moe": lambda num_classes=10, **kw: BlockDiffusionMoE(
        num_classes=num_classes, **kw),
    "net": lambda num_classes=10, **kw: Net(num_classes=num_classes, **kw),
    "resnet18": lambda num_classes=10, **kw: ResNet18(num_classes=num_classes, **kw),
    "resnet50": lambda num_classes=10, **kw: ResNet50(num_classes=num_classes, **kw),
}

# Models that understand the ResNet-only kwargs (fused Pallas stages etc.).
_RESNETS = {"resnet18", "resnet50"}

# The decoder's shape keys (`ModelConfig`): the trainer hands every model's
# factory the whole of the configuration's shapes, and a model is given
# what it takes.
DECODER_SHAPES = ("hidden_size", "num_layers", "num_heads", "num_kv_heads",
                  "head_dim", "expert_width", "num_experts",
                  "experts_per_token", "experts_held", "share_index",
                  "block_length", "rope_theta")
_DECODERS = {"sdar_moe"}

# Models carrying BatchNorm, i.e. the ones that accept ``axis_name`` for
# sync-BN inside shard_map (one source of truth — the trainer keys its
# sharded-update model construction off this, not a second name list).
BATCHNORM_MODELS = frozenset(_RESNETS)


def parse_fused_stages(spec: str | None) -> tuple[int, ...]:
    """Parse `ModelConfig.fused_stages`: '' -> none, 'all' -> all four
    stages, else comma-separated stage indices ('0' or '0,1,2,3')."""
    if not spec:
        return ()
    if spec.strip().lower() == "all":
        return (0, 1, 2, 3)
    try:
        stages = tuple(sorted({int(s) for s in spec.split(",") if s.strip()}))
    except ValueError:
        raise ValueError(
            f"fused_stages must be '', 'all', or comma-separated stage "
            f"indices, got {spec!r}") from None
    if any(s not in (0, 1, 2, 3) for s in stages):
        raise ValueError(
            f"fused_stages indices must be in 0..3, got {spec!r}")
    return stages


def build_model(name: str, num_classes: int = 10, **kwargs):
    """Construct a model by config name (`tpu_dp.config.ModelConfig.name`)."""
    key = name.lower()
    try:
        factory = _REGISTRY[key]
    except KeyError:
        raise ValueError(
            f"unknown model {name!r}; available: {sorted(_REGISTRY)}"
        ) from None
    if key not in _RESNETS:
        kwargs.pop("fused_stages", None)
        kwargs.pop("fused_block_b", None)
        kwargs.pop("fused_bwd", None)
    if key not in _DECODERS:
        for shape in DECODER_SHAPES:
            kwargs.pop(shape, None)
    return factory(num_classes=num_classes, **kwargs)


__all__ = [
    "BATCHNORM_MODELS", "BlockDiffusionMoE", "DECODER_SHAPES", "Net",
    "ResNet", "ResNet18", "ResNet50", "RowLoss", "build_model",
    "parse_fused_stages",
]

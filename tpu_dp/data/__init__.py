"""Data subsystem: datasets, sharded sampling, and the device feed.

TPU-native replacement for the reference's data layer — torchvision CIFAR
download + `DataLoader(num_workers=2)` + `DistributedSampler`
(`/root/reference/cifar_example.py:38-52`,
`/root/reference/cifar_example_ddp.py:61-76`). See the submodules:

- `cifar`     — CIFAR-10/100 pickle-batch loader + deterministic synthetic
- `sampler`   — `DistributedSampler`-contract host sharding
- `pipeline`  — batching, padding policy, prefetch-to-device
- `augment`   — on-device random crop + flip (compiled into the train step)
- `tokens`    — rows of token ids, through the same sampler and feeds
- `noise`     — block-diffusion masking noise (compiled into the train step)
"""

from tpu_dp.data.cifar import (
    ArrayDataset,
    load_dataset,
    make_synthetic,
    normalize,
)
from tpu_dp.data.pipeline import DataPipeline
from tpu_dp.data.sampler import ShardedSampler
from tpu_dp.data.tokens import TokenDataset, make_synthetic_tokens

__all__ = [
    "ArrayDataset",
    "DataPipeline",
    "ShardedSampler",
    "TokenDataset",
    "load_dataset",
    "make_synthetic",
    "make_synthetic_tokens",
    "normalize",
]

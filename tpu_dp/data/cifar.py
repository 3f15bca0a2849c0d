"""CIFAR-10/100 loading + deterministic synthetic data.

Replaces the reference's torchvision layer (`/root/reference/
cifar_example.py:38-52`): `torchvision.datasets.CIFAR10(download=True)` and
the `ToTensor + Normalize((0.5,)*3, (0.5,)*3)` transform. The build
environment has no network egress, so instead of downloading we read the
standard CIFAR python pickle-batch layout from `root` if present (the same
on-disk format torchvision extracts into `./data`) and otherwise fall back to
a deterministic synthetic dataset with the same shapes/dtypes — SURVEY.md §4
Integration: "short-run CIFAR-10 train on synthetic/cached data".

Datasets are plain in-memory uint8 NHWC arrays: the whole of CIFAR is
~180 MB, far below host RAM, and keeping it resident lets the pipeline do
zero-copy batch gathers. Normalization happens *on device*, fused into the
compiled step (`tpu_dp.train.step._maybe_normalize`) — shipping uint8 is 4×
less host→HBM traffic than float32.
"""

from __future__ import annotations

import dataclasses
import pickle
from pathlib import Path

import numpy as np

IMAGE_SHAPE = (32, 32, 3)

# Default sizes for the synthetic fallback — big enough for loss curves to
# move, small enough that CI stays fast.
_DEFAULT_SYNTHETIC_TRAIN = 1024
_DEFAULT_SYNTHETIC_TEST = 256


@dataclasses.dataclass(frozen=True)
class ArrayDataset:
    """An in-memory labeled image dataset.

    ``images`` is NHWC (uint8 from the loaders of this module, which check
    it); ``labels`` is int32. ``synthetic`` marks the
    no-real-data fallback so callers (and benchmark reports) can tell the
    difference.
    """

    images: np.ndarray
    labels: np.ndarray
    name: str
    num_classes: int
    synthetic: bool = False

    def __post_init__(self):
        assert len(self.images) == len(self.labels)

    def __len__(self) -> int:
        return len(self.images)

    @property
    def arrays(self) -> dict[str, np.ndarray]:
        """What a batch row is made of, keyed as the step reads its batch
        (the pipeline and the resident feed ship these and nothing else)."""
        return {"image": self.images, "label": self.labels}

    @property
    def items_per_row(self) -> int:
        """The counted items of a batch row: one image."""
        return 1

    @property
    def sample_input(self) -> np.ndarray:
        """One row as the model sees it (uint8 images reach it normalized
        to float32), for shape inference."""
        dtype = np.float32 if self.images.dtype == np.uint8 else self.images.dtype
        return np.zeros((1, *self.images.shape[1:]), dtype)


def _check_images(images: np.ndarray) -> np.ndarray:
    """What the image loaders promise: uint8 NHWC."""
    assert images.ndim == 4 and images.dtype == np.uint8
    return images


def normalize(images: np.ndarray) -> np.ndarray:
    """uint8 [0, 255] → float32 [-1, 1].

    Exactly the reference transform `ToTensor()` (÷255) then
    `Normalize((0.5,)*3, (0.5,)*3)` ((x−0.5)/0.5), i.e. x/255·2−1
    (`/root/reference/cifar_example.py:38-40`).
    """
    return images.astype(np.float32) * (2.0 / 255.0) - 1.0


def make_synthetic(
    num_examples: int,
    num_classes: int,
    seed: int = 0,
    name: str = "synthetic",
    example_seed: int | None = None,
) -> ArrayDataset:
    """Deterministic, learnable synthetic image classes.

    Each class is a fixed random uint8 template; examples are the template
    plus Gaussian pixel noise. Classes are far apart in pixel space, so a
    small CNN's loss falls quickly — giving the integration tests the same
    "loss decreases" signal the reference prints
    (`/root/reference/cifar_example.py:84-87`) without real data.

    Templates depend only on ``seed``. Labels/noise are drawn from a fresh
    ``example_seed`` stream when given; when ``example_seed`` is None they
    continue the template RNG stream (so the default is NOT equivalent to
    ``example_seed=seed``). Train/test splits of one synthetic "dataset"
    share ``seed`` (same classes — the test set is learnable from the train
    set) but use distinct example seeds (disjoint draws).
    """
    rng = np.random.default_rng(seed)
    templates = rng.integers(
        0, 256, size=(num_classes, *IMAGE_SHAPE), dtype=np.int16
    )
    rng_e = (
        rng if example_seed is None else np.random.default_rng(example_seed)
    )
    labels = rng_e.integers(0, num_classes, size=num_examples).astype(np.int32)
    noise = rng_e.normal(0.0, 24.0, size=(num_examples, *IMAGE_SHAPE))
    images = np.clip(templates[labels] + noise, 0, 255).astype(np.uint8)
    return ArrayDataset(
        images=_check_images(images), labels=labels, name=name,
        num_classes=num_classes, synthetic=True,
    )


def _read_pickle_batches(files: list[Path], label_key: bytes):
    """Read the standard CIFAR python pickle-batch layout.

    Same bytes torchvision extracts: a dict with b'data' of shape
    (N, 3072) uint8 in CHW order and a label list.
    """
    datas, labels = [], []
    for f in files:
        with open(f, "rb") as fh:
            d = pickle.load(fh, encoding="bytes")
        datas.append(np.asarray(d[b"data"], dtype=np.uint8))
        labels.extend(d[label_key])
    data = np.concatenate(datas, axis=0)
    images = data.reshape(-1, 3, 32, 32).transpose(0, 2, 3, 1)
    return (_check_images(np.ascontiguousarray(images)),
            np.asarray(labels, dtype=np.int32))


_SPECS = {
    "cifar10": dict(
        dirname="cifar-10-batches-py",
        train_files=[f"data_batch_{i}" for i in range(1, 6)],
        test_files=["test_batch"],
        label_key=b"labels",
        num_classes=10,
    ),
    "cifar100": dict(
        dirname="cifar-100-python",
        train_files=["train"],
        test_files=["test"],
        label_key=b"fine_labels",
        num_classes=100,
    ),
}


def load_dataset(
    name: str,
    root,
    train: bool = True,
    allow_synthetic: bool = True,
    synthetic_num_examples: int | None = None,
    seed: int = 0,
    seq_len: int = 0,
    vocab_size: int | None = None,
):
    """Load CIFAR-10/100 from ``root`` or fall back to synthetic data.

    ``name`` ∈ {cifar10, cifar100, synthetic, synthetic_tokens}; the last
    gives rows of ``seq_len`` token ids over ``vocab_size``
    (`tpu_dp.data.tokens`). The on-disk layout expected
    under ``root`` is what torchvision's downloader extracts into the
    reference's `./data` (`/root/reference/cifar_example.py:44-45`). When
    the files are absent and ``allow_synthetic``, a deterministic synthetic
    dataset with the right shapes and class count is returned (flagged via
    ``.synthetic``); otherwise FileNotFoundError.
    """
    name = name.lower()
    default_n = (
        _DEFAULT_SYNTHETIC_TRAIN if train else _DEFAULT_SYNTHETIC_TEST
    )
    n_synth = synthetic_num_examples or default_n
    # Same base seed (shared class templates across train/test), distinct
    # example seeds (disjoint noise/label draws).
    example_seed = seed * 2 + (0 if train else 1)

    if name == "synthetic_tokens":
        from tpu_dp.data.tokens import make_synthetic_tokens

        if not seq_len or not vocab_size:
            raise ValueError(
                "data.dataset=synthetic_tokens needs data.seq_len and "
                "model.num_classes (the vocabulary)")
        return make_synthetic_tokens(
            n_synth, seq_len, vocab_size, seed=seed,
            example_seed=example_seed)

    if name == "synthetic":
        return make_synthetic(
            n_synth, 10, seed=seed, name="synthetic",
            example_seed=example_seed,
        )

    if name not in _SPECS:
        raise ValueError(
            f"unknown dataset {name!r}; available: "
            f"{sorted(_SPECS) + ['synthetic', 'synthetic_tokens']}"
        )
    spec = _SPECS[name]
    base = Path(root) / spec["dirname"]
    files = [
        base / f for f in (spec["train_files"] if train else spec["test_files"])
    ]
    if all(f.exists() for f in files):
        images, labels = _read_pickle_batches(files, spec["label_key"])
        return ArrayDataset(
            images=images, labels=labels, name=name,
            num_classes=spec["num_classes"], synthetic=False,
        )
    if not allow_synthetic:
        raise FileNotFoundError(
            f"{name} not found under {base} and allow_synthetic=False; "
            f"expected files: {[f.name for f in files]}"
        )
    return make_synthetic(
        n_synth, spec["num_classes"], seed=seed, name=name,
        example_seed=example_seed,
    )

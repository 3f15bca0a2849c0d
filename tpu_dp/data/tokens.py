"""Token data sets: rows of ``L`` token ids, one document a row.

Beside `tpu_dp.data.cifar.ArrayDataset`, through the same sampler, pipeline
and resident feed: a data set is what `arrays` says it ships a batch row of,
keyed as the step reads its batch. The target of a token row is the row
itself, so a batch is ``{"tokens": [rows, L] int32}`` and nothing else.
"""

from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass(frozen=True)
class TokenDataset:
    """``tokens`` is int32 ``[rows, L]`` with ids in ``[0, vocab_size)``."""

    tokens: np.ndarray
    name: str
    vocab_size: int
    synthetic: bool = False

    def __post_init__(self):
        assert self.tokens.ndim == 2 and self.tokens.dtype == np.int32

    def __len__(self) -> int:
        return len(self.tokens)

    @property
    def num_classes(self) -> int:
        """What the model's head predicts over."""
        return self.vocab_size

    @property
    def arrays(self) -> dict[str, np.ndarray]:
        return {"tokens": self.tokens}

    @property
    def items_per_row(self) -> int:
        """The counted items of a batch row: its tokens."""
        return int(self.tokens.shape[1])

    @property
    def sample_input(self) -> np.ndarray:
        """A short row of the batch's dtype, for shape inference."""
        return np.zeros((1, min(self.tokens.shape[1], 64)), np.int32)


def make_synthetic_tokens(num_rows: int, length: int, vocab_size: int,
                          seed: int = 0, name: str = "synthetic_tokens",
                          example_seed: int | None = None) -> TokenDataset:
    """Rows of Zipf(1.0)-distributed ids: rank ``r`` has weight ``1/r``, and
    a seeded permutation says which id has which rank. The last id of the
    vocabulary is never drawn (a masked-diffusion model keeps it as its mask
    token). The permutation depends on ``seed`` alone, the rows on
    ``example_seed`` (train and test share a vocabulary's skew)."""
    ids = vocab_size - 1
    rng = np.random.default_rng(seed)
    rank_to_id = rng.permutation(ids).astype(np.int32)
    rng_e = rng if example_seed is None else np.random.default_rng(example_seed)
    cdf = np.cumsum(1.0 / np.arange(1, ids + 1))
    ranks = np.searchsorted(cdf, rng_e.random((num_rows, length)) * cdf[-1])
    return TokenDataset(np.ascontiguousarray(rank_to_id[ranks]), name,
                        vocab_size, synthetic=True)

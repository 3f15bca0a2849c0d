"""What a model hands the step in place of logits when its head and its
loss cannot be told apart (a vocabulary-sized head, chunked with its loss)."""

from __future__ import annotations

from typing import Any, NamedTuple


class RowLoss(NamedTuple):
    """A batch row's loss and what was judged in it, computed by the model.

    ``loss [rows]`` float32, the step's loss being its mean; ``correct
    [rows]`` and ``count [rows]`` int32, the judged items predicted right
    and their number; ``counters`` a float32 vector of the step's sums,
    named by the model's ``counter_names``."""

    loss: Any
    correct: Any
    count: Any
    counters: Any

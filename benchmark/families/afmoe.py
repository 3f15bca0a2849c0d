"""The family of decoders of sliding-window and full attention layers with
experts trained on the next token with AdamW: what `run.py`, `loop_hook.py`
and `calibrate.py` ask of a configuration's family (`benchmark/README.md`,
"What a family file answers"), answered from `reference_afmoe.py`,
`work_afmoe.py` and `datagen_sdar.py` (rows of Zipf ids; imported, not
edited). The model's shapes are the configuration file's own top-level keys,
under the names of the published config; the length of a row is the traffic
file's ``seq_len``.
"""

from __future__ import annotations

import datagen
import datagen_sdar
import reference_afmoe
import work_afmoe
from compare import leaf_norms

# The nearest precision below the stated bfloat16 first: the control.
PRECISIONS = ("float8", "bfloat16")
FAULTS = ("no_window", "no_gate", "rope_on_full", "no_shared_expert",
          "softmax_router")


def _length(job) -> int:
    return int(job.traffic["seq_len"])


def items_per_row(job) -> int:
    """A batch row's counted items are its tokens (the last one is an
    input's target only: ``L - 1`` positions are judged, ``L`` trained on)."""
    return _length(job)


def _tokens(job, device: bool = False):
    make = datagen_sdar.device_tokens if device else datagen_sdar.make_tokens
    return make(job.seed, job.train_size, _length(job),
                int(job.config["vocab_size"]))


def datasets(job):
    """The ``(train, test)`` host data sets of the seed; the test set is the
    first global batch (no run evaluates)."""
    from tpu_dp.data.tokens import TokenDataset

    tokens, vocab = _tokens(job), int(job.config["vocab_size"])
    name = job.config["name"]
    return (TokenDataset(tokens, name, vocab, synthetic=True),
            TokenDataset(tokens[:job.global_batch], name, vocab,
                         synthetic=True))


def init_params(job):
    """The seed's weights, made on the device in one call."""
    return reference_afmoe.init_params(job.config, job.seed)


def first_gradient(opt_state, params0, optimizer: dict):
    """The first gradient as AdamW got it, after the clip: its first moment
    after step 1 is ``(1 - b1) * g`` (the moments start at zero)."""
    import jax

    return jax.tree_util.tree_map(
        lambda m: m / (1.0 - float(optimizer["b1"])), opt_state["m"])


def variants(job) -> dict:
    return {"precisions": PRECISIONS, "faults": FAULTS}


def reference_readings(job, steps: int, precision: str = "float32",
                       fault: str | None = None) -> dict:
    """The plain reference's reading of the first ``steps`` steps: its own
    weights and rows from the seeds, a row at a time."""
    import jax
    import jax.numpy as jnp

    # The timed program is done with the chip: what is still loaded of it
    # (a loaded program keeps its scratch memory) goes, so that the
    # reference, which needs the same memory, has it.
    jax.clear_caches()
    dtype = {"float32": None, "bfloat16": jnp.bfloat16,
             "float8": jnp.float8_e4m3fn}[precision]
    tokens = _tokens(job, device=True)
    batches = [tokens[datagen.step_rows(job.program_seed, 0, job.train_size,
                                        job.global_batch, k)]
               for k in range(steps)]
    out = reference_afmoe.follow(
        job.config, job.config["optimizer"], lambda: init_params(job), batches,
        dtype=dtype, fault=fault, leaf_norms=leaf_norms)
    return {k: out[k] for k in ("loss", "grad1", "delta")}


def train_flops_per_item(job) -> float:
    return work_afmoe.train_flops_per_item(job.config, _length(job))


def least_step_seconds(job, peaks: dict) -> dict:
    return work_afmoe.least_step_seconds(
        job.config, _length(job), job.batch_per_chip,
        peaks["bf16_flops_per_s"], peaks["hbm_bytes_per_s"])

"""`correct` has to fail what it is there to catch.

Each test skips the harness's look for a chip (the rehearsal switch) and
drives the rest of a run at a size a test can hold, batch 16 on the CPU,
through `run.run` itself: the same trainer, hook, reference, comparison and
the cell's own limits. One run is sound; the others have the timed path
broken underneath, one fault each, or the lower-precision control put in
the program's place. Widths are the configuration's, so a run takes a
minute here.
"""

import copy

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import run
from compare import compare

SEED = 2147483747
REHEARSAL = {"batch_per_chip": 16, "train_size": 16 * 4}


def drive(cell_name, rehearsal=REHEARSAL, edit=None):
    cell = run.load_cell(cell_name)
    if edit is not None:
        edit(cell)
    return run.run(cell, SEED, 0.5, False, dict(rehearsal))


def numbers(result):
    return {row["name"]: row["value"] for row in result["compared"]}


@pytest.fixture(scope="module")
def sound():
    return drive("r18-b4096-resident")


def test_sound_run_is_correct_and_prints_no_metric(sound):
    assert sound["correct"] is True, sound["compared"]
    assert sound["metrics"] == {} and sound["rehearsal"] is True
    assert list(sound)[-1] == "compared"
    assert sound["attempted"] == 4 and sound["failed"] == 0


def test_float32_program_meets_the_reference_closely():
    """The reference is the program's own mathematics: with the program
    computing in float32 too, every number agrees to rounding."""
    def f32(cell):
        cell["config_file"] = copy.deepcopy(cell["config_file"])
        cell["config_file"]["argv"].append("--model.bf16=false")
    got = numbers(drive("r18-b4096-resident", edit=f32))
    assert max(got.values()) < 2e-3, got


def test_state_returned_unchanged_is_not_correct(monkeypatch):
    from tpu_dp.train.trainer import Trainer

    real = Trainer._resident_loop

    def broken(self, n):
        loop = real(self, n)

        def step(state, data, idx, *rest):
            kept = jax.tree_util.tree_map(jnp.copy, state)
            _, metrics = loop(state, data, idx, *rest)
            return kept, metrics
        return step

    monkeypatch.setattr(Trainer, "_resident_loop", broken)
    result = drive("r18-b4096-resident")
    assert result["correct"] is False
    got = numbers(result)
    assert got["grad1_worst_leaf"] >= 0.99 and got["delta3_worst_leaf"] >= 0.99


def test_half_the_batch_left_out_is_not_correct(monkeypatch):
    from tpu_dp.data.pipeline import DataPipeline

    real = DataPipeline._index_windows_iter

    def halved(self, k, skip_steps=0):
        for n, idx in real(self, k, skip_steps):
            rows = np.asarray(idx)
            half = rows.shape[-1] // 2
            rows = np.concatenate([rows[..., :half], rows[..., :half]], -1)
            yield n, jax.device_put(rows, idx.sharding)

    monkeypatch.setattr(DataPipeline, "_index_windows_iter", halved)
    assert drive("r18-b4096-resident")["correct"] is False


def test_exchange_left_out_is_not_correct(monkeypatch):
    """Four chips, each given chip 0's rows: what every chip computes when
    nothing is exchanged, the batch statistics with it."""
    from tpu_dp.data.pipeline import DataPipeline

    real = DataPipeline._index_windows_iter

    def local_only(self, k, skip_steps=0):
        for n, idx in real(self, k, skip_steps):
            rows = np.asarray(idx)
            quarter = rows.shape[-1] // 4
            rows = np.concatenate([rows[..., :quarter]] * 4, -1)
            yield n, jax.device_put(rows, idx.sharding)

    monkeypatch.setattr(DataPipeline, "_index_windows_iter", local_only)
    rehearsal = {"batch_per_chip": 4, "train_size": 16 * 4}
    assert drive("r18-dp4-b4096", rehearsal)["correct"] is False


def test_sound_run_on_four_devices_meets_every_limit_on_the_norms():
    """The four-chip cell also compares the loss, to 2e-4: a limit read at
    16,384 rows. Sixteen rows in bfloat16 round ten times coarser, so at this
    size the loss is held to 2e-3 and the norms to the cell's own limits."""
    rehearsal = {"batch_per_chip": 4, "train_size": 16 * 4}
    result = drive("r18-dp4-b4096", rehearsal)
    assert result["device"]["count"] == 4
    for row in result["compared"]:
        limit = 2e-3 if row["name"].startswith("loss_step") else row["limit"]
        assert row["value"] <= limit, row


def test_float8_control_is_not_correct():
    """The reference computed in float8, put in the program's place."""
    cell = run.load_cell("r18-b4096-resident")
    family, job = cell["family"], run.job_of(cell, SEED, REHEARSAL)
    control = family.variants(job)["precisions"][0]
    assert control == "float8"
    ref = family.reference_readings(job, 3)
    got = family.reference_readings(job, 3, precision=control)
    correct, rows = compare(got, ref, cell["limits"])
    assert correct is False, rows

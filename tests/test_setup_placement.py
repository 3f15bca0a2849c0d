"""The train state meets every dispatch where the step returns it
(`Trainer._place_state`), so each train program is made once.

A train program's jit keys its cache on where each argument sits. A state
left uncommitted on one device (a fresh one), or with leaves a caller
swapped in, makes the program again at its second dispatch: traced,
lowered and compiled or loaded a second time. The trainer places the fresh
state where it keeps it and, before an epoch's first dispatch, whatever
leaf is not on its target; (a) each program then holds one cache entry,
the compile listener reads it traced once, ``setup.step_entries`` counts
one a program, and the recompile guard warms up with one call; (a') with
the placement taken out the same run reads two; (b) on one device the
placed leaves keep their buffers, and a placed state is returned as it is;
(c) the lowered program is the same whether its state was placed or not;
(d) the state an epoch's loop left goes unchecked into the next epoch, a
swapped one is placed.
"""

from __future__ import annotations

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from test_loop_spans import _isolate_global_counters  # noqa: F401
from tpu_dp.obs import compiles
from tpu_dp.obs import counters as global_counters
from tpu_dp.parallel.sharding import replicated_sharding
from tpu_dp.train.trainer import Trainer

pytestmark = pytest.mark.obs


def _lenet(tmp_path, resident: str, devices: int | None = None) -> Trainer:
    from tpu_dp.config import Config

    c = Config()  # LeNet, four steps an epoch
    c.data.dataset = "synthetic"
    c.data.synthetic_train_size = 64
    c.data.synthetic_test_size = 16
    c.data.batch_size = 16
    c.data.device_resident = resident
    c.train.log_every = 100
    c.train.ckpt_dir = str(tmp_path / "ck")
    if devices is not None:
        c.parallel.num_devices = devices
    return Trainer(c)


def _sdar(tmp_path, resident: str) -> Trainer:
    from test_sdar_train import tiny_cfg, token_sets

    return Trainer(tiny_cfg(tmp_path, **{"data.device_resident": resident}),
                   datasets=token_sets())


TRAINERS = {
    "lenet-resident": lambda p: _lenet(p, "on"),
    "lenet-streamed": lambda p: _lenet(p, "off"),
    "sdar-resident": lambda p: _sdar(p, "auto"),
}


def _swap(trainer: Trainer, what: str) -> None:
    """What a caller does to the state between construction and the first
    epoch: the benchmark's weights committed to the replicated sharding in
    the place of the program's (`benchmark/run.py`), or a whole state
    made afresh and left where it was made."""
    if what == "params":
        params = jax.device_put(
            jax.tree_util.tree_map(jnp.array, trainer.state.params),
            replicated_sharding(trainer.mesh))
        trainer.state = trainer.state.replace(params=params)
    else:
        trainer.state = trainer._fresh_state()


def _entries(trainer: Trainer) -> list[int]:
    return [p.run._cache_size() for p in trainer._programs.values()]


def _traces(trainer: Trainer) -> list[float]:
    table = compiles.install().table
    return [table[p.run.__name__]["traces"]
            for p in trainer._programs.values()]


# ------------------------------------------------------ (a) made once

@pytest.mark.parametrize("what", ["params", "fresh"])
@pytest.mark.parametrize("name", sorted(TRAINERS))
def test_each_train_program_is_made_once(tmp_path, name, what):
    trainer = TRAINERS[name](tmp_path)
    _swap(trainer, what)
    trainer.train_epoch(0)
    assert global_counters.get("setup.step_entries") == len(
        trainer._programs)
    trainer.train_epoch(1)
    assert trainer._programs
    assert _entries(trainer) == [1] * len(trainer._programs)
    assert _traces(trainer) == [1] * len(trainer._programs)
    for prog in trainer._programs.values():
        assert prog.run.warmup_calls == 1
        assert prog.run.retraces == 0
    assert trainer.train_step.retraces == 0
    assert global_counters.get("recompile.retraces") == 0


def test_the_counter_reads_a_second_making_without_the_placement(
        tmp_path, monkeypatch):
    monkeypatch.setattr(Trainer, "_place_state", lambda self, state: state)
    trainer = _lenet(tmp_path, "on")
    _swap(trainer, "params")
    trainer.train_epoch(0)
    assert global_counters.get("setup.step_entries") == 2
    trainer.train_epoch(1)
    assert _entries(trainer) == [2]
    assert _traces(trainer) == [2]
    # The guard, warmed up with one call, names the second making.
    (prog,) = trainer._programs.values()
    assert prog.run.retraces == 1
    assert global_counters.get("recompile.retraces") == 1


def test_the_setup_line_names_the_step_entries(tmp_path, monkeypatch):
    lines = []
    monkeypatch.setattr(compiles.install(), "log",
                        lambda msg, *args: lines.append(msg % args))
    trainer = _lenet(tmp_path, "on")
    trainer.train_epoch(0)
    (summary,) = [ln for ln in lines if ln.startswith("set-up:")]
    assert "compiled anew), step entries 1, trace " in summary
    assert re.search(r"loop [0-9.]+ s \(traced 1x", summary)


# ---------------------------------------------- (b) no second copy, ever

def _pointers(state) -> list[int]:
    return [x.addressable_shards[0].data.unsafe_buffer_pointer()
            for x in jax.tree_util.tree_leaves(state)]


def test_on_one_device_the_placed_leaves_keep_their_buffers(tmp_path):
    trainer = _lenet(tmp_path, "on", devices=1)
    target = replicated_sharding(trainer.mesh)
    fresh = trainer._fresh_state()
    leaves = jax.tree_util.tree_leaves(fresh)
    assert not any(x.committed for x in leaves)
    before = _pointers(fresh)
    placed = trainer._place_state(fresh)
    assert _pointers(placed) == before
    assert all(x.committed and x.sharding == target
               for x in jax.tree_util.tree_leaves(placed))
    # In place already: the same state, not a copy of it.
    assert trainer._place_state(placed) is placed
    assert trainer._place_state(trainer.state) is trainer.state
    # A caller's committed weights stay the objects they were; only the
    # leaves off their target move.
    params = jax.device_put(jax.tree_util.tree_map(jnp.array, fresh.params),
                            target)
    mixed = trainer._fresh_state().replace(params=params)
    again = trainer._place_state(mixed)
    assert all(a is b for a, b in zip(jax.tree_util.tree_leaves(again.params),
                                      jax.tree_util.tree_leaves(params)))
    assert _pointers(again) == _pointers(mixed)


def test_a_host_state_is_placed_on_every_device(tmp_path):
    trainer = _lenet(tmp_path, "on")
    host = jax.tree_util.tree_map(np.asarray, trainer.state)
    placed = trainer._place_state(host)
    target = replicated_sharding(trainer.mesh)
    for x, h in zip(jax.tree_util.tree_leaves(placed),
                    jax.tree_util.tree_leaves(host)):
        assert x.committed and x.sharding == target
        np.testing.assert_array_equal(np.asarray(x), h)


# ------------------------------------- (c) the compiled step is the same

@pytest.mark.parametrize("resident", ["on", "off"])
def test_the_lowered_program_is_the_same_placed_or_not(tmp_path, resident):
    trainer = _lenet(tmp_path, resident)
    if trainer.resident_train is not None:
        _, idx = next(iter(trainer.train_pipe.index_windows(1)))
        fed = (trainer.resident_train, idx)
    else:
        _, batch = next(iter(trainer.train_pipe.windows(1)))
        fed = (batch,)
    run = trainer._program(1).run
    fresh = trainer._fresh_state()
    assert not jax.tree_util.tree_leaves(fresh)[0].committed

    def text(state):
        return re.sub(r"loc\(.*?\)", "", run.lower(state, *fed).as_text())

    assert text(fresh) == text(trainer._place_state(fresh))


# ------------------------------------------ (d) what the epoch's gap pays

def test_the_loops_own_state_goes_unchecked_and_a_swapped_one_is_placed(
        tmp_path, monkeypatch):
    trainer = _lenet(tmp_path, "on")
    trainer.train_epoch(0)
    checked = []
    place = Trainer._place_state
    monkeypatch.setattr(Trainer, "_place_state",
                        lambda self, state: checked.append(state)
                        or place(self, state))
    trainer.train_epoch(1)
    assert checked == []  # the epoch's gap pays nothing for it
    _swap(trainer, "fresh")
    trainer.train_epoch(2)
    assert len(checked) == 1
    assert _entries(trainer) == [1]

"""The step's device phases are names in HLO metadata, and nothing else.

`tpu_dp.input`, `.augment`, `.gather`, `.fwd_bwd`, `.grad_reduce` and
`.update` (`train/step.py`) are `jax.named_scope`s: a device trace can be
grouped by them only if every costly op carries exactly one, and they may
not move a collective.
"""

from __future__ import annotations

import contextlib
import re

import jax
import pytest

from tpu_dp.analysis import hlo

PHASES = {"tpu_dp.input", "tpu_dp.augment", "tpu_dp.gather",
          "tpu_dp.fwd_bwd", "tpu_dp.grad_reduce", "tpu_dp.update"}
#: What a step spends its time in, and what a trace would group by phase.
COSTLY = ("convolution", "dynamic-slice", "dynamic-update-slice", "while",
          "all-reduce", "gather")
_OP = re.compile(r" = .*?[\])}] ([a-z\-]+)\(")
_OP_NAME = re.compile(r'op_name="([^"]*)"')


def _step_program(tmp_path, model: str, resident: str):
    """The program a benchmark cell runs, and its arguments, at batch 16 on
    four devices: the resident window of one step, or the streamed step."""
    from tpu_dp.config import Config
    from tpu_dp.train.trainer import Trainer

    c = Config()
    c.data.dataset = "synthetic"
    c.data.synthetic_train_size = 64
    c.data.synthetic_test_size = 16
    c.data.batch_size = 16
    c.data.augment = True
    c.data.device_resident = resident
    c.model.name = model
    c.parallel.num_devices = 4
    c.train.ckpt_dir = str(tmp_path / f"ck_{model}_{resident}")
    tr = Trainer(c)
    if tr.resident_train is not None:
        _, idx = next(iter(tr.train_pipe.index_windows(1)))
        args = (tr.state, tr.resident_train, idx)
    else:
        _, batch = next(iter(tr.train_pipe.windows(1)))
        args = (tr.state, batch)
    return tr._program(1).run, args


def _phases_by_op(text: str) -> list[tuple[str, str | None, set]]:
    """(op kind, op_name, phases in it) of every costly op of an HLO text."""
    out = []
    for line in text.splitlines():
        m = _OP.search(line)
        if m is None or m.group(1) not in COSTLY:
            continue
        name = _OP_NAME.search(line)
        name = name.group(1) if name else None
        out.append((m.group(1), name,
                    set(re.findall(r"tpu_dp\.\w+", name or ""))))
    return out


def _one_image_at_a_time(text: str, phase: str) -> list[str]:
    """Ops of ``phase`` that could move data a row of the batch at a time:
    a gather, a dynamic slice or update, or a loop whose state holds a
    floating-point array. (The CPU compiler keeps the threefry rounds of
    the phase's random draws as `while`s over `u32` state, five trips
    whatever the batch; the TPU's unrolls them, and
    `tests/test_tpu_compile.py` asks it for no `while` at all.)"""
    out = []
    for line in text.splitlines():
        m = _OP.search(line)
        if m is None or phase not in line:
            continue
        kind = m.group(1)
        if kind in ("gather", "dynamic-slice", "dynamic-update-slice") or (
                kind == "while"
                and re.search(r"\b(f|bf)\d+\[", line.split(" while(")[0])):
            out.append(line.strip()[:200])
    return out


@pytest.mark.parametrize("resident", ["on", "off"])
def test_every_costly_op_carries_one_phase(tmp_path, resident):
    fn, args = _step_program(tmp_path, "resnet18", resident)
    text, _, _ = hlo.lower_and_compile(fn, args)
    ops = _phases_by_op(text)
    kinds = {k for k, _, _ in ops}
    assert {"convolution", "all-reduce", "gather"} <= kinds
    for kind, name, phases in ops:
        assert len(phases) <= 1 and phases <= PHASES, (kind, name)
    named = [(k, n) for k, n, p in ops if len(p) == 1]
    bare = [(k, n) for k, n, p in ops if not p]
    if resident == "on":
        assert bare == []
    else:
        # The CPU compiler rewrites the streamed step's weight-gradient
        # convolutions and gives the new ops no metadata at all: no name
        # to lose a phase from. Every op that has a name has its phase.
        assert all(k == "convolution" and n is None for k, n in bare), bare
        assert len(named) > 2 * len(bare)
    seen = {k: {next(iter(p)) for kk, _, p in ops if kk == k and p}
            for k in kinds}
    assert seen["convolution"] == {"tpu_dp.fwd_bwd"}
    assert seen["all-reduce"] == {"tpu_dp.fwd_bwd"}
    # The crop is a phase of its own, apart from the input's
    # normalisation, and moves the whole batch at once: selects among
    # static shifts, nothing that takes one image at a time. The resident
    # feed's gather has a phase of its own.
    assert re.search(r" select\(.*tpu_dp\.augment", text)
    assert _one_image_at_a_time(text, "tpu_dp.augment") == []
    assert "tpu_dp.augment" not in seen["gather"]
    assert ("tpu_dp.gather" in seen["gather"]) == (resident == "on")


@pytest.mark.parametrize("resident", ["on", "off"])
def test_scopes_leave_the_collective_fingerprint_alone(tmp_path, monkeypatch,
                                                       resident):
    """Metadata only: the program traced with every `named_scope` taken
    out has the same collective schedule, op for op."""
    with_scopes = hlo.program_fingerprint(
        *_step_program(tmp_path / "with", "net", resident))
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    fn, args = _step_program(tmp_path / "without", "net", resident)
    text, _, _ = hlo.lower_and_compile(fn, args)
    assert not re.search(r"tpu_dp\.\w+", text)
    assert hlo.schedule_digest(hlo.collect_ops(text)) == with_scopes


def test_the_reshape_after_the_gather_is_of_its_phase(tmp_path):
    """The resident feed gathers flat rows and gives a row its shape back
    (`train/step.py:gather_rows`): that reshape belongs to `tpu_dp.gather`
    and to no other phase. Read in the lowered program, since a compiler
    may fold a reshape away (the CPU's makes it a bitcast of the gather)."""
    fn, args = _step_program(tmp_path, "resnet18", "on")
    text = fn.lower(*args).as_text(debug_info=True)
    locs = dict(re.findall(r'^(#loc\d+) = loc\("([^"]*)"', text, re.M))
    restored = re.findall(
        r"stablehlo\.reshape .*\(tensor<16x3072xui8>\) -> "
        r"tensor<16x32x32x3xui8> loc\((#loc\d+)\)", text)
    assert len(restored) == 1
    name = locs[restored[0]]
    assert name == "tpu_dp.gather/reshape"
    # and nothing else reshapes under the gather's name: rows of rank 1 (the
    # labels) are gathered as they are
    assert sum(n.startswith("tpu_dp.gather/reshape")
               for n in locs.values()) == 1

"""`tools/fused_verdict.py` renders the round's fused-vs-unfused decision
from the bench archive — the logic that picks which rows form each
comparison cell and what the verdict line says must not quietly drift.
"""

from __future__ import annotations

import json
import sys

from tools import fused_verdict


def _row(value, ts, batch=2048, window=30, fused="", bwd=False, xent="jnp",
         backend="tpu", mfu=0.5, smoke=False):
    r = {"metric": "cifar10_resnet18_train_images_per_sec_per_chip",
         "value": value, "ts": ts, "backend": backend, "mfu": mfu,
         "config": {"per_chip_batch": batch, "steps_per_call": window,
                    "fused_stages": fused, "fused_bwd": bwd, "xent": xent}}
    if smoke:
        r["smoke"] = True
    return r


def _write(monkeypatch, tmp_path, rows):
    p = tmp_path / "results.jsonl"
    p.write_text("".join(json.dumps(r) + "\n" for r in rows))
    monkeypatch.setattr(fused_verdict, "RESULTS", p)


def _verdict_line(capsys):
    out = capsys.readouterr().out
    return next(l for l in out.splitlines() if l.startswith("VERDICT:")), out


def test_smoke_cpu_and_pallas_xent_rows_excluded(monkeypatch, tmp_path,
                                                 capsys):
    _write(monkeypatch, tmp_path, [
        _row(34000, "2026-07-30T01:00:00Z"),
        # smoke on tpu backend and plain cpu backend pin the two filters
        # independently (a smoke row is not necessarily backend=cpu).
        _row(9.9, "2026-07-30T02:00:00Z", smoke=True),
        _row(7.7, "2026-07-30T02:30:00Z", backend="cpu"),
        _row(50000, "2026-07-30T03:00:00Z", xent="pallas"),
    ])
    monkeypatch.setattr(sys, "argv", ["fused_verdict.py"])
    fused_verdict.main()
    line, out = _verdict_line(capsys)
    # The unfused cell must be the tpu/jnp row — not the newer pallas-xent
    # row, not the smoke row.
    assert "34,000" in out and "50,000" not in out
    assert "9.9" not in out and "7.7" not in out


def test_winning_variant_flips_the_verdict(monkeypatch, tmp_path, capsys):
    _write(monkeypatch, tmp_path, [
        _row(34000, "2026-07-30T01:00:00Z"),
        _row(36000, "2026-07-30T01:00:00Z", fused="0"),
        _row(33000, "2026-07-30T01:00:00Z", fused="all"),
    ])
    monkeypatch.setattr(sys, "argv", ["fused_verdict.py"])
    fused_verdict.main()
    line, _ = _verdict_line(capsys)
    assert "BEATS" in line and "fused[0]" in line and "+5.9%" in line


def test_losing_variants_keep_default_off(monkeypatch, tmp_path, capsys):
    _write(monkeypatch, tmp_path, [
        _row(34000, "2026-07-30T01:00:00Z"),
        _row(31000, "2026-07-30T01:00:00Z", fused="all", bwd=True),
    ])
    monkeypatch.setattr(sys, "argv", ["fused_verdict.py"])
    fused_verdict.main()
    line, _ = _verdict_line(capsys)
    assert "no fused variant beats unfused" in line
    assert "fused[all]+bwd" in line and "-8.8%" in line


def test_newest_row_wins_a_cell(monkeypatch, tmp_path, capsys):
    _write(monkeypatch, tmp_path, [
        _row(30000, "2026-07-29T01:00:00Z"),
        _row(34000, "2026-07-30T01:00:00Z"),  # newer same cell
        _row(35000, "2026-07-30T02:00:00Z", fused="0"),
    ])
    monkeypatch.setattr(sys, "argv", ["fused_verdict.py"])
    fused_verdict.main()
    line, out = _verdict_line(capsys)
    assert "34,000" in line and "30,000" not in out


def test_pending_without_fused_measurements(monkeypatch, tmp_path, capsys):
    _write(monkeypatch, tmp_path, [_row(34000, "2026-07-30T01:00:00Z")])
    monkeypatch.setattr(sys, "argv", ["fused_verdict.py"])
    fused_verdict.main()
    line, _ = _verdict_line(capsys)
    assert "pending" in line

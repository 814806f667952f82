"""CPU rehearsal of chip_smoke.py: the cv phase's plumbing — archive
from a seed, the driver's normal entry point, journal, byte and loss
checks, the last line — at a tiny geometry, with the platform check
told to expect `cpu` from here (the program has no switch for it)."""
import json
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402

# `--test` is the driver's own shrink (one-channel ResNet9, a 1 x 10
# table); the byte check follows from the same numbers
TINY_CV = dict(num_workers=8, local_batch_size=4, num_clients=16,
               k=10, num_rows=1, num_cols=10, rounds=6, scan_span=2,
               images_per_batch_file=40, grad_size=100,
               extra=("--iid", "--test"))


@pytest.fixture
def tiny(monkeypatch, tmp_path):
    monkeypatch.setattr(chip_smoke, "EXPECT_PLATFORM", "cpu")
    monkeypatch.setattr(chip_smoke, "CV", TINY_CV)
    monkeypatch.chdir(tmp_path)         # the drivers write runs/ here
    return str(tmp_path / "out")


def test_cv_phase_rehearsal_prints_the_contract_line(tiny, capsys):
    rc = chip_smoke.main(["--phase", "cv", "--out", tiny])
    out = capsys.readouterr().out.strip().splitlines()
    assert rc == 0
    assert json.loads(out[-1]) == {
        "ok": True,
        "device": {"platform": "cpu", "kind": "cpu", "count": 8}}
    text = "\n".join(out[:-1])
    for phase in ("cv/per-round", "cv/scan"):
        assert f"[{phase}] rounds=6" in text
        assert f"[{phase}] smoke readings, not a benchmark" in text
    assert "sync probe" in text and "download accounting path" in text


def test_failing_phase_exits_nonzero_without_the_line(tiny, capsys,
                                                      monkeypatch):
    # a driver that bills other bytes than the table's fails the phase
    monkeypatch.setattr(chip_smoke, "CV", dict(TINY_CV, num_cols=11))
    with pytest.raises(SystemExit) as e:
        chip_smoke.main(["--phase", "cv", "--out", tiny])
    assert e.value.code not in (0, None)
    assert '"ok"' not in capsys.readouterr().out


def test_wrong_platform_runs_nothing(monkeypatch, capsys, tmp_path):
    # EXPECT_PLATFORM stays "tpu"; the suite runs on the CPU
    rc = chip_smoke.main(["--out", str(tmp_path / "out")])
    captured = capsys.readouterr()
    assert rc != 0 and '"ok"' not in captured.out
    assert not os.path.exists(tmp_path / "out")

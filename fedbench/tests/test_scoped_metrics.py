"""The per-scope and per-span readers (`metrics/_scopes.py`) on a tiny
traced cell on the CPU, through a manifest of their own
(`tests/scoped/manifest.json`: the tiny configuration and traffic,
the root manifest's per-layer entries). The CPU backend's trace has
no `tf_op` and no TPU plane, so the device readers give None there;
the journal readers give numbers. None of them raises."""
from __future__ import annotations

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

SCOPED = os.path.join(HERE, "scoped", "manifest.json")
NEW = ("client_fwdbwd_ms", "encode_ms", "decode_select_ms",
       "server_update_ms", "device_unscoped_ms", "loader_fetch_ms",
       "loader_assemble_ms", "host_wait_ms", "idle_unattributed_ms")


def test_scoped_manifest_mirrors_the_root_manifest():
    """The test manifest's per-layer entries are the root manifest's,
    name for name and key for key (cells renamed)."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        root = json.load(f)["per_layer"]
    with open(SCOPED) as f:
        mine = json.load(f)["per_layer"]
    strip = lambda e: {k: v for k, v in e.items() if k != "workloads"}
    assert [strip(e) for e in mine] == [strip(e) for e in root]
    assert {e["name"] for e in root} >= set(NEW)
    for e in root:
        assert os.path.isfile(os.path.join(
            BENCH, "metrics", e["name"] + ".py"))


@pytest.mark.parametrize("workload", ["scoped_sketch",
                                      "scoped_localtopk"])
def test_new_readers_on_a_tiny_traced_cell(workload, capfd):
    from fedbench import harness
    # 3 s with the first 0.5 s under the profiler: the program's
    # TRACE writes a few times a second, so the journal holds untraced spans
    result = harness.run(workload, 2 ** 31 + 17, 3.0, True,
                         manifest_path=SCOPED, expect_platform=None)
    got = result["metrics"]
    # what the cell reported before is still there
    assert {"host_stage_ms", "host_api_ms", "device_busy_ms",
            "device_idle"} <= set(got)
    # the journal readers find the program's spans
    for name in ("loader_fetch_ms", "loader_assemble_ms",
                 "host_wait_ms"):
        assert got[name]["value"] >= 0.0 and got[name]["unit"] == "ms"
    assert got["loader_fetch_ms"]["value"] > 0.0
    # `load` is made of its three children, and is what the
    # benchmark's own span around next(stream) sees
    assert (got["loader_fetch_ms"]["value"]
            + got["loader_assemble_ms"]["value"]
            <= got["host_stage_ms"]["value"] * 1.5)
    # no TPU plane, no tf_op: the device readers leave the line alone
    for name in ("client_fwdbwd_ms", "encode_ms", "decode_select_ms",
                 "server_update_ms", "device_unscoped_ms",
                 "idle_unattributed_ms"):
        assert name not in got
    err = capfd.readouterr().err
    assert "[fedbench] scopes: the trace names no layer scope" in err


def test_readers_without_a_run_give_none():
    """No run directory, no spans (a program older than the scopes):
    every reader returns None and none raises."""
    from fedbench import harness
    ctx = {"cell": "no_such_cell", "rounds": 3, "program_spans": [],
           "window_s": 1.0, "trace": {"modules": {}, "busy_s": 0.0}}
    for name in NEW:
        reader = harness.load_module(
            os.path.join(BENCH, "metrics", name + ".py"), "t_" + name)
        assert reader.read(ctx) is None

"""Tests of the benchmark's own code, at sizes a CPU test run holds.

    JAX_PLATFORMS=cpu python -m pytest fedbench/tests -q

They live under `fedbench/` because a benchmark PR adds files nowhere
else. The configuration they drive (`tests/tiny/`) is not in
BENCHMARK.json. No test describes a TPU topology.
"""
from __future__ import annotations

import json
import os
import re
import subprocess
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

TINY = os.path.join(HERE, "tiny", "manifest.json")
RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def _run(workload, trace=False, **kw):
    from fedbench import harness
    return harness.run(workload, 2 ** 31 + 11, 0.5, trace,
                       manifest_path=TINY, expect_platform=None, **kw)


@pytest.mark.parametrize("workload", ["tiny_sketch", "tiny_localtopk",
                                      "tiny_gpt2_sketch"])
def test_end_to_end_result_line(workload, capsys):
    """The harness end to end: the last line of standard output is one
    JSON object with the contract's keys, the end-to-end metrics of the
    cell, and the numbers compared under the last key."""
    from fedbench import harness
    result = _run(workload)
    harness.print_result(result)
    captured = capsys.readouterr()
    line = captured.out.strip().splitlines()[-1]
    obj = json.loads(line)
    assert RESULT_KEYS <= set(obj)
    assert list(obj)[-1] == "checks"
    assert {"round_ms", "setup_s"} <= set(obj["metrics"])
    assert all(v["value"] > 0 for v in obj["metrics"].values())
    assert obj["attempted"] > 0 and obj["failed"] == 0
    assert set(obj["device"]) == {"platform", "kind", "count",
                                  "memory_peak_bytes"}
    assert obj["checks"]["compiles_in_window"]["value"] == 0
    # the comparison's numbers, each beside its limit, end stderr
    tail = captured.err.strip().splitlines()[-len(obj["checks"]):]
    assert all(t.startswith("[fedbench] check ") and "limit=" in t
               for t in tail)
    # sound runs of both modes agree with the plain reference on the
    # first gradient and the losses to rounding
    assert obj["checks"]["loss_gap"]["value"] < 1e-5
    assert obj["checks"]["first_grad_gap"]["value"] < 1e-5


def test_traced_run_reports_per_layer_metrics():
    result = _run("tiny_localtopk", trace=True)
    assert {"host_stage_ms", "host_api_ms", "device_busy_ms",
            "state_motion_ms", "device_idle"} <= set(result["metrics"])
    # no table of peaks for the CPU: a share of a peak is left out,
    # never reported as 0
    assert "step_mfu" not in result["metrics"]
    dev = result["device"]
    assert 0 < dev["busy_s"] <= dev["window_s"]
    assert len(result["breakdown"]["device_ops"]) <= 10
    assert len(result["breakdown"]["idle_gaps"]) <= 10


@pytest.mark.parametrize("workload,fault", [
    ("tiny_sketch", "frozen"), ("tiny_sketch", "half_batch"),
    ("tiny_localtopk", "frozen"), ("tiny_localtopk", "half_batch")])
def test_fault_in_the_timed_path_reads_incorrect(workload, fault):
    """The rest of a run with the timed path broken underneath: a
    step that hands back the state it was given, and half of the
    cohort left out of the mean."""
    result = _run(workload, fault=fault)
    assert result["correct"] is False
    over = {k for k, c in result["checks"].items()
            if c["value"] > c["limit"]}
    assert "change_gap" in over
    if fault == "half_batch":
        assert "loss_gap" in over


def test_lower_precision_control_reads_incorrect():
    """The control: the program's own bfloat16 path against the same
    reference fails the comparison."""
    result = _run("tiny_sketch", bf16=True)
    assert result["correct"] is False
    assert (result["checks"]["loss_gap"]["value"]
            > 10 * result["checks"]["loss_gap"]["limit"])


def test_benchmark_cell_refuses_the_cpu():
    """A BENCHMARK.json cell started where JAX finds no TPU exits
    non-zero and prints no result."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        cell = json.load(f)["workloads"][0]["name"]
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         cell, "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert "metrics" not in proc.stdout
    assert "runs on 'tpu' only" in proc.stderr


def test_reduce_on_the_recorded_trace():
    """reduce.py on the trace recorded on the chip
    (`tests/record_trace.py`): busy, window and per-module seconds as
    worked out by hand from a dump of its events
    (`testdata/small.expected.json` says how)."""
    from fedbench import reduce as reducer
    with open(os.path.join(BENCH, "testdata",
                           "small.expected.json")) as f:
        want = json.load(f)
    got = reducer.reduce_trace(
        os.path.join(BENCH, "testdata", "small.xplane.pb"))
    assert got["devices"] == want["devices"]
    assert got["busy_s"] == pytest.approx(want["busy_s"], rel=1e-9)
    assert got["window_s"] == pytest.approx(want["window_s"], rel=1e-9)
    for module, seconds in want["modules"].items():
        assert got["modules"][module] == pytest.approx(seconds, rel=1e-9)
        assert got["module_runs"][module] == want["module_runs"][module]
    assert got["busy_s"] < got["window_s"]
    names = [n for n, _ in reducer.breakdown(got)["idle_gaps"]]
    assert any(n.startswith("all:") for n in names)


def test_interval_arithmetic():
    from fedbench import reduce as reducer
    iv = [(0.0, 1.0), (0.5, 2.0), (3.0, 4.0), (3.2, 3.4)]
    assert reducer.union_seconds(iv) == pytest.approx(3.0)
    assert reducer.gaps_of(iv) == [(2.0, 3.0)]
    assert reducer.module_key("jit_round_step(123)") == "jit_round_step"


def test_work_counts_against_hand_counts():
    work = _load("work/resnet9_cifar10.py")
    with open(os.path.join(BENCH, "configs",
                           "resnet9_cifar10.json")) as f:
        config = json.load(f)
    # by hand: prep 2*32*32*9*3*64 = 3,538,944; each of the seven
    # other 3x3 layers (five distinct shapes, all equal) 150,994,944
    # or two halves of it; head 2*512*10
    fwd = 3_538_944 + 5 * 150_994_944 + 10_240
    assert work.forward_flops_per_example(config) == fwd == 758_523_904
    assert work.train_flops_per_example(config) == 3 * fwd - 3_538_944
    # GPT2-small per position at L=255: twelve blocks of 24 E^2 +
    # 2 E (L + 1), and the tied head 2 E V; flash forward at
    # (4, 12, 256, 64): 4 * 12 * (256 * 257 / 2) pairs * 64 * 4
    gpt2 = _load("work/gpt2_small_personachat.py")
    with open(os.path.join(BENCH, "configs",
                           "gpt2_small_personachat.json")) as f:
        g = json.load(f)
    per_pos = 12 * (24 * 768 * 768 + 2 * 768 * 256) + 2 * 768 * 50262
    assert gpt2.forward_flops_per_position(g, 255) == per_pos == 251_790_336
    assert gpt2.train_flops_per_example(
        g, {"corpus": {"max_tokens": 255}}) == 3 * per_pos * 2 * 255
    flash = gpt2.flash_forward_work(4, 12, 256, 64)
    assert flash["flops"] == 4 * 12 * 32896 * 64 * 4 == 404_226_048
    assert flash["bytes"] == 4 * 12 * (4 * 256 * 64 * 4 + 256 * 4)


def test_reference_sketch_and_top_k():
    """The per-coordinate sketch is linear, recovers a heavy vector's
    coordinates, and top-k keeps the k largest magnitudes."""
    from fedbench import reference as ref
    sk = ref.Sketch(d=1000, c=128, r=5, seed=42)
    rng = np.random.RandomState(0)
    a = rng.randn(1000).astype(np.float32)
    b = rng.randn(1000).astype(np.float32)
    np.testing.assert_allclose(sk.encode(a) + sk.encode(b),
                               sk.encode(a + b), atol=1e-4)
    heavy = np.zeros(1000, np.float32)
    heavy[[3, 400, 999]] = [5.0, -7.0, 9.0]
    est = sk.estimates(sk.encode(heavy))
    np.testing.assert_allclose(est[[3, 400, 999]], [5.0, -7.0, 9.0])
    kept = ref.top_k_dense(np.array([1.0, -5.0, 3.0, 0.5], np.float32), 2)
    np.testing.assert_array_equal(kept, [0.0, -5.0, 3.0, 0.0])


NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def test_manifest_lint():
    """Names and units use the allowed characters; every per-layer
    metric moves an end-to-end metric that each of its cells reports;
    every file the manifest names exists."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        m = json.load(f)
    assert set(m) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    cells = {w["name"]: w for w in m["workloads"]}
    configs = {c["name"]: c for c in m["configs"]}
    e2e = {e["name"]: e for e in m["end_to_end"]}
    assert "setup_s" in e2e and 1 <= m["run_seconds"] <= 51
    for name in [*cells, *configs, *e2e,
                 *(p["name"] for p in m["per_layer"])]:
        assert NAME.match(name), name
    for metric in [*m["end_to_end"], *m["per_layer"]]:
        assert UNIT.match(metric["unit"]), metric
        assert metric["better"] in ("lower", "higher")
        assert metric["source"] in ("device_trace", "program_span",
                                    "program_counter", "host_clock")
    for e in m["end_to_end"]:
        assert 0 < e["bound"] <= 0.1
        assert e["source"] in ("host_clock", "device_trace")
    for c in configs.values():
        assert os.path.isfile(os.path.join(ROOT, c["file"]))
        with open(os.path.join(ROOT, c["file"])) as f:
            cfg = json.load(f)
        for kind, key in (("drivers", "driver"), ("configs", "reference"),
                          ("work", "work")):
            assert os.path.isfile(os.path.join(
                BENCH, kind, cfg[key] + ".py")), (kind, cfg[key])
        assert any(w["config"] == c["name"] for w in cells.values())
    for w in cells.values():
        assert w["config"] in configs and w["chips"] in (1, 4)
        assert NAME.match(w["traffic"]) and len(w["why"]) <= 200
        assert os.path.isfile(os.path.join(
            BENCH, "traffic", w["traffic"] + ".json"))

    def reporting(metric):
        return set(metric.get("workloads", cells))

    for p in m["per_layer"]:
        assert os.path.isfile(os.path.join(
            BENCH, "metrics", p["name"] + ".py")), p["name"]
        assert p["moves"] in e2e
        assert reporting(p) <= reporting(e2e[p["moves"]]) <= set(cells)
    layers = {p["layer"] for p in m["per_layer"]}
    assert all("\n" not in layer and len(layer) <= 200 for layer in layers)
    for name in cells:
        assert any(name in reporting(p) for p in m["per_layer"])
        assert sum(name in reporting(e) for e in m["end_to_end"]) >= 2
    with open(os.path.join(BENCH, "peaks.json")) as f:
        peaks = json.load(f)
    assert peaks["source"] and "TPU v5 lite" in peaks["devices"]


def _load(rel):
    from fedbench import harness
    return harness.load_module(os.path.join(BENCH, rel),
                               "t_" + rel.replace("/", "_")[:-3])


class _Stop(Exception):
    pass


@pytest.mark.parametrize("weights_seed,seed,expect", [
    (None, 2 ** 31 + 11, 2 ** 31 + 11),
    (7, 2 ** 31 + 11, 7),
    (7, 12, 7),
])
def test_weights_come_from_the_configurations_seed(weights_seed, seed,
                                                   expect, tmp_path):
    """A configuration that states `weights_seed` is one model under
    every `--seed` (the seed then draws the traffic alone: cohorts,
    rows, order); one that does not takes its weights from `--seed`.
    The SmallThinker cell needs the first: its round time follows the
    routing its random weights give."""
    from fedbench import harness, traffic as traffic_mod

    cell = harness.Cell(os.path.join(HERE, "tiny_smallthinker",
                                     "manifest.json"),
                        "tiny_smallthinker_unc")
    config = dict(cell.config)
    if weights_seed is not None:
        config["weights_seed"] = weights_seed
    seen = []

    class Ref:
        @staticmethod
        def init_params(cfg, s):
            seen.append(s)
            raise _Stop

    data_dir = traffic_mod.ensure_corpus(
        cell.traffic, os.path.join(cell.bench_dirs[0], ".cache"))
    with pytest.raises(_Stop):
        cell.driver.build(config, cell.traffic, Ref, seed, data_dir,
                          str(tmp_path / "journal.jsonl"))
    assert seen == [expect]

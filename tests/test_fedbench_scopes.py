"""The benchmark's per-scope and per-span readers (ISSUE 27,
`fedbench/metrics/_scopes.py` and the nine `metrics/<name>.py`) over
the trace recorded on the chip (`fedbench/testdata/scoped.xplane.pb`,
by `fedbench/tests/record_scoped_trace.py`) with a hand-built `ctx`,
against `scoped.expected.json`, which was worked out from a dump made
with another parser (its `how` says how)."""
import json
import os
import shutil
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
BENCH = os.path.join(ROOT, "fedbench")
TESTDATA = os.path.join(BENCH, "testdata")

# the journal of the made-up run: two rounds while the profiler was on
# (what the harness hands over as ctx["program_spans"]) and three after
TRACED = [
    {"name": "round", "t0": 10.0, "dur": 0.09, "round": 0},
    {"name": "collect", "t0": 10.05, "dur": 0.04, "round": 0},
    {"name": "device_wait", "t0": 10.05, "dur": 0.039, "round": 0},
    {"name": "load_fetch", "t0": 10.1, "dur": 0.5, "clients": 4},
    {"name": "round", "t0": 10.7, "dur": 0.09, "round": 1},
]
UNTRACED = [
    {"name": "load", "t0": 11.0, "dur": 0.013, "seq": 2},
    {"name": "load_sample", "t0": 11.0, "dur": 0.001},
    {"name": "load_fetch", "t0": 11.001, "dur": 0.009, "clients": 4},
    {"name": "load_assemble", "t0": 11.01, "dur": 0.003, "bytes": 64},
    {"name": "round", "t0": 11.02, "dur": 0.02, "round": 2},
    {"name": "device_wait", "t0": 11.03, "dur": 0.004, "round": 2},
    {"name": "device_wait", "t0": 11.035, "dur": 0.002, "round": 2,
     "of": 1},
    {"name": "load_fetch", "t0": 11.05, "dur": 0.011, "clients": 4},
    {"name": "load_assemble", "t0": 11.061, "dur": 0.001, "bytes": 64},
    {"name": "round", "t0": 11.07, "dur": 0.02, "round": 3},
    {"name": "device_wait", "t0": 11.08, "dur": 0.003, "round": 3},
    {"name": "round", "t0": 11.1, "dur": 0.02, "round": 4},
]
NEW = ("client_fwdbwd_ms", "encode_ms", "decode_select_ms",
       "server_update_ms", "device_unscoped_ms", "loader_fetch_ms",
       "loader_assemble_ms", "host_wait_ms", "idle_unattributed_ms")


@pytest.fixture(scope="module")
def want():
    with open(os.path.join(TESTDATA, "scoped.expected.json")) as f:
        return json.load(f)


@pytest.fixture
def ctx(tmp_path, want):
    """A run directory laid out as the harness lays one out, and the
    `ctx` its traced window hands the readers."""
    from fedbench import reduce as reducer
    from fedbench.metrics import _scopes
    _scopes._cache.clear()
    profile = tmp_path / "trace" / "plugins" / "profile" / "1"
    profile.mkdir(parents=True)
    shutil.copyfile(os.path.join(TESTDATA, "scoped.xplane.pb"),
                    profile / "scoped.xplane.pb")
    with open(tmp_path / "journal.jsonl", "w") as f:
        f.write(json.dumps({"event": "run_start"}) + "\n")
        f.write(json.dumps({"event": "trace", "controller": 0,
                            "spans": TRACED}) + "\n")
        f.write(json.dumps({"event": "trace", "controller": 0,
                            "spans": UNTRACED}) + "\n")
        f.write('{"event": "trace", "spans": [{"name": "torn')
    return {"cell": "recorded", "run_dir": str(tmp_path),
            "rounds": want["rounds"], "window_s": 0.03,
            "program_spans": TRACED,
            "trace": reducer.reduce_trace(
                str(profile / "scoped.xplane.pb"))}


def _read(name, ctx):
    from fedbench import harness
    return harness.load_module(
        os.path.join(BENCH, "metrics", name + ".py"),
        "t_metric_" + name).read(ctx)


def test_scope_seconds_match_the_hand_count(ctx, want, capsys):
    from fedbench.metrics import _scopes
    got = _scopes.scope_seconds(ctx)
    assert set(got["scopes"]) == set(want["scope_s"])
    for name, seconds in want["scope_s"].items():
        assert got["scopes"][name] == pytest.approx(seconds, rel=1e-9)
    for module, seconds in want["unscoped_s"].items():
        assert got["unscoped"][module] == pytest.approx(seconds, rel=1e-9)
    for module, seconds in want["pathless_s"].items():
        assert got["pathless"][module] == pytest.approx(seconds, rel=1e-9)
        # the cumsum's three ops have a path (`reduce_window_sum:`)
        # and no scope; the rest of the unscoped ops have no path
        assert 0 < seconds < got["unscoped"][module] or \
            module != "jit_round_step"
    for module, seconds in want["module_s"].items():
        assert got["modules"][module] == pytest.approx(seconds, rel=1e-9)
        # scoped + unscoped ops of a module fit inside its runs
        assert got["unscoped"][module] < seconds
    # the outermost name wins: fed_server_state/fed_encode/... is
    # server_state, and `encode` has no op of its own
    assert "encode" not in got["scopes"]
    err = capsys.readouterr().err
    assert err.count("[fedbench] device ms a round per scope:") == 1
    _scopes.scope_seconds(ctx)          # kept: worked out once a run
    assert "[fedbench]" not in capsys.readouterr().err


@pytest.mark.parametrize("name,scopes", [
    ("client_fwdbwd_ms", ["fwdbwd"]),
    ("encode_ms", []),                  # scopes laid, none of these: 0.0
    ("decode_select_ms", ["select"]),
    ("server_update_ms", ["server_state"]),
])
def test_scope_readers(name, scopes, ctx, want):
    ms = sum(want["scope_s"][s] for s in scopes) / want["rounds"] * 1e3
    got = _read(name, ctx)
    assert got == pytest.approx(ms, rel=1e-9) and (got > 0) == bool(scopes)


def test_unscoped_reader_counts_the_round_program_only(ctx, want):
    assert _read("device_unscoped_ms", ctx) == pytest.approx(
        want["unscoped_s"]["jit_round_step"] / want["rounds"] * 1e3,
        rel=1e-9)


def test_idle_goes_to_the_innermost_program_span(ctx, want, capsys):
    from fedbench.metrics import _scopes
    got = _scopes.idle_by_span(ctx)
    assert got["idle"] == pytest.approx(want["idle_s"], rel=1e-9)
    assert set(got["by_span"]) == set(want["idle_by_span_s"])
    for name, seconds in want["idle_by_span_s"].items():
        assert got["by_span"][name] == pytest.approx(seconds, rel=1e-6)
    assert got["unattributed"] == pytest.approx(
        want["unattributed_s"], rel=1e-6)
    assert (sum(got["by_span"].values()) + got["unattributed"]
            == pytest.approx(got["idle"], rel=1e-9))
    assert _read("idle_unattributed_ms", ctx) == pytest.approx(
        want["unattributed_s"] / want["rounds"] * 1e3, rel=1e-6)
    err = capsys.readouterr().err
    assert "[fedbench] idle ms a round per program span:" in err
    # one clock: each round's dispatch starts after its stage ended,
    # and its round program's first op within a millisecond of it
    # (this small program starts at once, and the trace's host and
    # device clocks differ by a millisecond or two: PERF.md section 3)
    line = next(x for x in err.splitlines() if "one clock" in x)
    rows = json.loads(line[line.index("]: ") + 3:])
    assert [r[0] for r in rows] == [0, 1, 2, 3]
    assert all(-0.2 < r[1] < 0 and -2.0 < r[2] < 2.0 for r in rows)


def test_journal_readers_take_the_untraced_part(ctx):
    # after the last traced span (10.79): two batches, three rounds
    assert _read("loader_fetch_ms", ctx) == pytest.approx(10.0)
    assert _read("loader_assemble_ms", ctx) == pytest.approx(2.0)
    assert _read("host_wait_ms", ctx) == pytest.approx(9.0 / 3)


def test_readers_give_none_for_a_program_without_scopes_or_spans(ctx):
    """The parent commit under these readers: a trace whose ops carry
    `tf_op` but no scope (the trace PR 25 recorded), no `fed:*`
    annotation, no journal span."""
    from fedbench.metrics import _scopes
    profile = os.path.join(ctx["run_dir"], "trace", "plugins", "profile")
    shutil.rmtree(profile)
    os.makedirs(os.path.join(profile, "1"))
    shutil.copyfile(os.path.join(TESTDATA, "small.xplane.pb"),
                    os.path.join(profile, "1", "small.xplane.pb"))
    os.remove(os.path.join(ctx["run_dir"], "journal.jsonl"))
    _scopes._cache.clear()
    ctx["program_spans"] = []
    planes = _scopes.read_device_planes(
        os.path.join(profile, "1", "small.xplane.pb"))
    assert any("dot_general" in t for p in planes.values()
               for t in p["tf_op"].values())
    for name in NEW:
        assert _read(name, ctx) is None


def test_wire_reader_agrees_with_profile_data():
    """The XSpace wire reader against `jax.profiler.ProfileData` on
    both recorded traces: the same device-op events, name for name, to
    the nanosecond."""
    from fedbench import reduce as reducer
    from fedbench.metrics import _scopes
    for name in ("small", "scoped"):
        path = os.path.join(TESTDATA, name + ".xplane.pb")
        mine = _scopes.read_device_planes(path)
        theirs = reducer.read_events(path)["devices"]
        assert set(mine) == set(theirs)
        for plane, dev in theirs.items():
            ops = mine[plane]["lines"][reducer.OP_LINE]
            assert len(ops) == len(dev["ops"]) > 0
            for (meta, t0, t1), (ev_name, a, b, _) in zip(ops, dev["ops"]):
                assert mine[plane]["names"][meta] == ev_name
                assert abs(t0 - a) < 2e-9 and abs(t1 - b) < 2e-9


@pytest.mark.parametrize("path,scope", [
    ("jit(round_step)/jit(main)/fed_fwdbwd/conv_general_dilated:",
     "fwdbwd"),
    ("jit(round_step)/transpose(jvp(fed_fwdbwd))/dot_general:", "fwdbwd"),
    ("jit(round_step)/vmap(fed_residual)/mul:", "residual"),
    ("jit(f)/fed_server_state/fed_encode/reduce_sum:", "server_state"),
    ("jit(f)/fed_select/select_n:", "select"),
    ("jit(f)/select_n:", None),
    ("jit(f)/gather:", None),
    ("jit(unfed_select)/add:", None),
    ("fed_encode", "encode"),
    ("reduce_window_sum:", None),
])
def test_scope_of_a_path(path, scope):
    from fedbench.metrics import _scopes
    assert _scopes.scope_of(path) == scope


def test_gaps_split_at_span_borders():
    from fedbench.metrics import _scopes
    spans = [("round", 0.0, 10.0, 1), ("stage", 1.0, 3.0, 1),
             ("collect", 5.0, 9.0, 1), ("device_wait", 6.0, 8.0, 1)]
    got = _scopes.attribute_gaps([(0.5, 2.0), (2.5, 7.0), (9.5, 12.0)],
                                 spans)
    assert got["idle"] == pytest.approx(8.5)
    assert got["by_span"] == pytest.approx({
        "round": 0.5 + 2.0 + 0.5, "stage": 1.0 + 0.5,
        "collect": 1.0, "device_wait": 1.0})
    assert got["unattributed"] == pytest.approx(2.0)

"""graftscope tracing tests (ISSUE 13): tracer/ring semantics, the
`trace` journal-event schema, cross-thread span stitching (writer
threads carry the producing round), neutrality (tracing on vs off is
ServerState bit-identical and transfer-guard clean; tracing OFF adds
zero journal writes), the stage analytics (per-stage p50/p95, cadence,
overlap efficiency), and the Perfetto exporter's Chrome trace JSON.
"""
import importlib.util
import json
import os
import threading

import jax.numpy as jnp
import numpy as np
import pytest

from commefficient_tpu.config import Config
from commefficient_tpu.federated.api import FedModel, FedOptimizer
from commefficient_tpu.telemetry import RunJournal, TelemetrySession
from commefficient_tpu.telemetry.journal import (
    summarize, validate_journal,
)
from commefficient_tpu.telemetry.trace import (
    TRACE, Tracer, overlap_efficiency, stage_stats,
)
from commefficient_tpu.utils.checkpoint import AsyncCheckpointWriter

D = 8


@pytest.fixture(autouse=True)
def _trace_off_after():
    """TRACE is process-global: never let an enable leak across
    tests (the same guarantee TelemetrySession.close gives runs)."""
    yield
    TRACE.disable()


def _load_script(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "scripts", f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def loss_fn(params, batch, mask):
    x, y = batch
    pred = x @ params["w"]
    per_ex = 0.5 * (pred - y) ** 2
    denom = jnp.maximum(mask.sum(), 1.0)
    loss = (per_ex * mask).sum() / denom
    return loss, (loss,)


def _fed_model(**kw):
    base = dict(mode="uncompressed", grad_size=D, weight_decay=0.0,
                num_workers=8, local_momentum=0.0,
                virtual_momentum=0.9, error_type="none",
                microbatch_size=-1, num_clients=8)
    base.update(kw)
    model = FedModel(None, loss_fn, Config(**base),
                     params={"w": jnp.zeros(D)})
    opt = FedOptimizer(model)
    opt.param_groups[0]["lr"] = 0.1
    return model, opt


def _rounds(R, seed=0):
    rng = np.random.RandomState(seed)
    w_true = rng.randn(D).astype(np.float32)
    out = []
    for _ in range(R):
        x = rng.randn(8, 4, D).astype(np.float32)
        y = np.einsum("wbd,d->wb", x, w_true).astype(np.float32)
        out.append((np.arange(8, dtype=np.int32), (x, y),
                    np.ones((8, 4), np.float32)))
    return out


def _span_args(rs):
    return (np.stack([s[0] for s in rs]),
            tuple(np.stack([s[1][i] for s in rs]) for i in range(2)),
            np.stack([s[2] for s in rs]),
            np.full(len(rs), 0.1, np.float32))


# ---------------- tracer mechanics -----------------------------------------

def test_disabled_tracer_is_inert_and_allocation_free():
    tr = Tracer(enabled=False)
    s1 = tr.span("stage")
    s2 = tr.span("other", round=3)
    # the disabled fast path hands out ONE shared no-op object
    assert s1 is s2
    with s1:
        pass
    tr.instant("mark")
    tr.record("device_execute", 0.0, 1.0)
    spans, dropped = tr.drain()
    assert spans == [] and dropped == 0
    assert tr.current_tags() == {}


def test_span_records_duration_and_tags():
    t = [100.0]
    tr = Tracer(enabled=True, clock=lambda: t[0])
    with tr.span("dispatch", round=4, span=2):
        t[0] = 100.25
    spans, dropped = tr.drain()
    assert dropped == 0
    (rec,) = spans
    assert rec["name"] == "dispatch"
    assert rec["round"] == 4 and rec["span"] == 2
    assert rec["t0"] == 100.0 and rec["dur"] == 0.25
    assert rec["thread"] == threading.current_thread().name


def test_nested_spans_inherit_correlation_tags():
    tr = Tracer(enabled=True)
    with tr.span("plan", round=7, span=1):
        assert tr.current_tags() == {"round": 7, "span": 1}
        with tr.span("plan_install"):
            pass
        tr.instant("journal_enqueue", seq=0, q=2)
    spans, _ = tr.drain()
    by_name = {r["name"]: r for r in spans}
    # round/span flow down; explicit tags never get overwritten
    assert by_name["plan_install"]["round"] == 7
    assert by_name["plan_install"]["span"] == 1
    assert by_name["journal_enqueue"]["round"] == 7
    assert by_name["journal_enqueue"]["seq"] == 0
    assert by_name["journal_enqueue"]["q"] == 2
    assert tr.current_tags() == {}  # stack unwound


def test_ring_overflow_drops_and_counts():
    tr = Tracer(enabled=True, ring_size=3)
    for i in range(5):
        tr.instant("m", i=i)
    spans, dropped = tr.drain()
    assert len(spans) == 3 and dropped == 2
    # drain resets both the ring and the drop counter
    spans, dropped = tr.drain()
    assert spans == [] and dropped == 0


def test_drain_sorts_across_threads_by_t0():
    tr = Tracer(enabled=True)
    tr.record("b", 2.0, 3.0)

    def other():
        tr.record("a", 1.0, 1.5)

    th = threading.Thread(target=other, name="other-thread")
    th.start()
    th.join()
    spans, _ = tr.drain()
    assert [r["name"] for r in spans] == ["a", "b"]
    assert {r["thread"] for r in spans} == {
        threading.current_thread().name, "other-thread"}


# ---------------- stage analytics ------------------------------------------

def test_stage_stats_p50_p95():
    spans = [{"name": "stage", "dur": d / 100.0}
             for d in range(1, 101)]
    spans.append({"name": "junk", "dur": "not-a-number"})
    stats = stage_stats(spans)
    assert set(stats) == {"stage"}
    assert stats["stage"]["n"] == 100
    assert stats["stage"]["p50_s"] == pytest.approx(0.51)
    assert stats["stage"]["p95_s"] == pytest.approx(0.96)
    assert stats["stage"]["total_s"] == pytest.approx(50.5)


def test_overlap_efficiency_takes_interval_union():
    # two overlapping device windows [0,2] and [1,3] inside a 4s wall:
    # union busy = 3s, NOT the 4s a naive sum would claim
    spans = [
        {"name": "device_execute", "t0": 0.0, "dur": 2.0},
        {"name": "device_execute", "t0": 1.0, "dur": 2.0},
        {"name": "collect", "t0": 3.0, "dur": 1.0},
    ]
    assert overlap_efficiency(spans) == pytest.approx(0.75)
    assert overlap_efficiency([{"name": "collect", "t0": 0.0,
                                "dur": 1.0}]) is None
    assert overlap_efficiency([]) is None


# ---------------- journal schema -------------------------------------------

def test_trace_event_schema_valid(tmp_path):
    p = str(tmp_path / "j.jsonl")
    j = RunJournal(p)
    j.event("trace", controller=0, spans=[
        {"name": "dispatch", "thread": "MainThread", "t0": 1.5,
         "dur": 0.25, "round": 3}])
    j.close()
    records, problems = validate_journal(p)
    assert problems == []
    # every record carries the monotonic twin of `ts`
    assert all(isinstance(r.get("mono"), float) for r in records)


@pytest.mark.parametrize("bad", [
    {"spans": "not-a-list"},
    {"spans": [{"thread": "t", "t0": 0.0, "dur": 0.1}]},     # no name
    {"spans": [{"name": "x", "t0": 0.0, "dur": 0.1}]},       # no thread
    {"spans": [{"name": "x", "thread": "t", "dur": 0.1}]},   # no t0
    {"spans": [{"name": "x", "thread": "t", "t0": -1.0,
                "dur": 0.1}]},                               # negative
    {"spans": [], "dropped": -3},
    {"spans": ["not-an-object"]},
])
def test_trace_event_schema_rejects_malformed(tmp_path, bad):
    p = str(tmp_path / "j.jsonl")
    j = RunJournal(p)
    j.event("trace", controller=0, **bad)
    j.close()
    _, problems = validate_journal(p)
    assert problems, f"malformed trace record passed: {bad}"


def test_negative_mono_rejected(tmp_path):
    p = str(tmp_path / "j.jsonl")
    RunJournal(p, mono_clock=lambda: -5.0).event("x")
    _, problems = validate_journal(p)
    assert any("mono" in pr for pr in problems)


def test_summarize_overlap_segments_at_run_start():
    """A resumed/takeover journal holds trace spans from TWO process
    lifetimes with unrelated monotonic bases; the overlap math must
    sum busy/wall per segment, never span the inter-base gap."""
    def seg(base):
        return {"v": 1, "event": "trace", "ts": 0.0, "mono": base,
                "spans": [
                    {"name": "device_execute", "thread": "MainThread",
                     "t0": base, "dur": 1.0},
                    {"name": "collect", "thread": "MainThread",
                     "t0": base + 1.0, "dur": 1.0}]}
    records = [
        {"v": 1, "event": "run_start", "ts": 0.0, "mono": 10.0},
        seg(10.0),
        # second process: mono base 1e6 away — mixing extents would
        # make wall ~1e6 s and overlap ~0
        {"v": 1, "event": "run_start", "ts": 0.0, "mono": 1e6},
        seg(1e6),
    ]
    s = summarize(records)
    # each segment: 1 s busy in a 2 s wall -> 0.5 overall
    assert s["overlap_efficiency"] == pytest.approx(0.5)
    assert s["trace_spans"] == 4


# ---------------- cross-thread stitching -----------------------------------

def test_async_journal_writer_spans_stitch_to_producing_round(tmp_path):
    p = str(tmp_path / "j.jsonl")
    TRACE.enable(controller=0)
    j = RunJournal(p, async_writer=True)
    j.event("round", round=5, loss=1.0)
    j.flush()
    j.close()
    spans, _ = TRACE.drain()
    by_name = {}
    for r in spans:
        by_name.setdefault(r["name"], []).append(r)
    enq = [r for r in by_name.get("journal_enqueue", [])
           if r.get("round") == 5]
    assert enq, f"no enqueue instant for round 5 in {spans}"
    seq = enq[0]["seq"]
    qwait = [r for r in by_name.get("journal_qwait", [])
             if r.get("seq") == seq]
    write = [r for r in by_name.get("journal_write", [])
             if r.get("seq") == seq]
    # the writer-thread spans pair with the producer's enqueue by
    # `seq` and inherit the producing round — recorded on a DIFFERENT
    # thread than the enqueue
    assert qwait and write
    assert qwait[0]["round"] == 5 and write[0]["round"] == 5
    assert qwait[0]["thread"] == "journal-writer"
    assert write[0]["thread"] == "journal-writer"
    assert enq[0]["thread"] != write[0]["thread"]


def test_trace_flush_itself_is_never_traced(tmp_path):
    """The batched `trace` append must not generate its own
    journal_write span — that would self-feed one span per flush
    forever."""
    p = str(tmp_path / "j.jsonl")
    TRACE.enable(controller=0)
    j = RunJournal(p)
    j.event("trace", controller=0, spans=[])
    j.close()
    spans, _ = TRACE.drain()
    assert spans == []


def test_checkpoint_writer_spans_stitch_to_producing_round(tmp_path):
    TRACE.enable(controller=0)
    done = []
    w = AsyncCheckpointWriter(name="ckpt")
    try:
        with TRACE.span("checkpoint", round=9):
            w.submit(lambda: done.append(1))
        w.drain()
    finally:
        w.close()
    spans, _ = TRACE.drain()
    assert done == [1]
    by_name = {r["name"]: r for r in spans}
    assert by_name["ckpt_enqueue"]["round"] == 9
    seq = by_name["ckpt_enqueue"]["seq"]
    assert by_name["ckpt_qwait"]["seq"] == seq
    assert by_name["ckpt_write"]["seq"] == seq
    # queue-wait + write happen ON the writer thread, tagged with the
    # round captured on the PRODUCER thread
    assert by_name["ckpt_write"]["round"] == 9
    assert by_name["ckpt_write"]["thread"] == "ckpt-writer"


# ---------------- neutrality -----------------------------------------------

def test_tracing_on_off_bit_identical_state(tmp_path):
    finals = []
    for trace_on in (True, False):
        model, _ = _fed_model()
        sess = TelemetrySession(
            journal=RunJournal(str(tmp_path / f"j{trace_on}.jsonl")),
            trace=trace_on)
        model.attach_telemetry(sess)
        stream = _rounds(6)
        for ids, data, mask in stream[:2]:
            model((ids, data, mask))
        model.run_rounds(*_span_args(stream[2:]))
        sess.close()
        assert TRACE.enabled is False  # close() always disables
        finals.append(model.server)
    a, b = finals
    np.testing.assert_array_equal(np.asarray(a.ps_weights),
                                  np.asarray(b.ps_weights))
    np.testing.assert_array_equal(np.asarray(a.Vvelocity),
                                  np.asarray(b.Vvelocity))
    np.testing.assert_array_equal(np.asarray(a.Verror),
                                  np.asarray(b.Verror))
    assert int(a.round_idx) == int(b.round_idx) == 6


def test_traced_span_dispatch_transfer_guard_clean(tmp_path, sanitize):
    model, _ = _fed_model()
    sess = TelemetrySession(
        journal=RunJournal(str(tmp_path / "j.jsonl")), trace=True)
    model.attach_telemetry(sess)
    stream = _rounds(6)
    model.run_rounds(*_span_args(stream[:3]))  # compile outside guard
    with sanitize.forbid_transfers():
        model.run_rounds(*_span_args(stream[3:]))
    sess.close()


def test_tracing_off_adds_zero_journal_writes(tmp_path):
    """The bounded-overhead contract: with --trace off (the default)
    the journal stream is exactly what it was before graftscope —
    no `trace` events, same record kinds, and the global tracer's
    rings stay empty through a full run."""
    model, _ = _fed_model()
    jpath = str(tmp_path / "j.jsonl")
    sess = TelemetrySession(journal=RunJournal(jpath))
    model.attach_telemetry(sess)
    for ids, data, mask in _rounds(3):
        model((ids, data, mask))
    sess.close()
    spans, dropped = TRACE.drain()
    assert spans == [] and dropped == 0
    records, problems = validate_journal(jpath)
    assert problems == []
    assert all(r["event"] != "trace" for r in records)


# ---------------- end-to-end: journal -> analytics -> Perfetto -------------

def _traced_run(tmp_path, n=6):
    model, _ = _fed_model()
    jpath = str(tmp_path / "traced.jsonl")
    sess = TelemetrySession(journal=RunJournal(jpath), trace=True,
                            controller=0)
    model.attach_telemetry(sess)
    stream = _rounds(n)
    for ids, data, mask in stream[:2]:
        model((ids, data, mask))
    model.run_rounds(*_span_args(stream[2:]))
    sess.close()
    return jpath


def test_traced_run_journal_validates_with_stage_analytics(tmp_path):
    jpath = _traced_run(tmp_path)
    records, problems = validate_journal(jpath)
    assert problems == []
    traces = [r for r in records if r["event"] == "trace"]
    assert traces, "traced run journaled no trace events"
    summary = summarize(records)
    assert summary["trace_spans"] > 0
    stages = summary["trace_stages"]
    # the round lifecycle is covered: planning, staging, dispatch,
    # the device window, and collection all have p50/p95 entries
    for stage in ("plan", "stage", "dispatch", "device_execute",
                  "collect", "gather", "round_dispatch", "scatter"):
        assert stage in stages, f"missing stage {stage!r}"
        assert stages[stage]["n"] > 0
        assert stages[stage]["p95_s"] >= stages[stage]["p50_s"] >= 0
    assert summary["overlap_efficiency"] is not None
    assert 0 < summary["overlap_efficiency"] <= 1.0
    # 6 rounds with `mono` stamps -> a cadence block with a histogram
    assert summary["cadence"]["rounds"] == 5
    assert sum(summary["cadence"]["hist"].values()) == 5


def test_trace_export_chrome_json(tmp_path):
    jpath = _traced_run(tmp_path)
    te = _load_script("trace_export")
    out = str(tmp_path / "out.trace.json")
    assert te.main([jpath, "-o", out]) == 0
    with open(out) as f:
        trace = json.load(f)
    evs = trace["traceEvents"]
    xs = [e for e in evs if e.get("ph") == "X"]
    assert xs, "no complete events exported"
    for e in xs:
        assert isinstance(e["name"], str)
        assert e["ts"] >= 0 and e["dur"] >= 0
        assert isinstance(e["pid"], int) and isinstance(e["tid"], int)
    # ISSUE 13 acceptance: >= 5 distinct stages
    assert len({e["name"] for e in xs}) >= 5
    # process/thread metadata rows name every (pid, tid) used
    named = {(m["pid"], m.get("tid")) for m in evs
             if m.get("ph") == "M" and m["name"] == "thread_name"}
    assert {(e["pid"], e["tid"]) for e in xs} <= named
    # spans tagged with their producing round survive into args
    assert any(e.get("args", {}).get("round") is not None for e in xs)


def test_trace_export_empty_journal_fails_loud(tmp_path):
    p = str(tmp_path / "j.jsonl")
    RunJournal(p).event("run_start")
    te = _load_script("trace_export")
    assert te.main([p, "-o", str(tmp_path / "o.json")]) == 1


# ---------------- ISSUE 27: spans on the profiler's clock ------------------

class _FakeAnnotation:
    """Stands in for jax.profiler.TraceAnnotation: records what was
    constructed and that each was entered and left once."""
    made = []

    def __init__(self, name, **kwargs):
        self.name, self.kwargs = name, kwargs
        self.entered = self.left = 0
        _FakeAnnotation.made.append(self)

    def __enter__(self):
        self.entered += 1
        return self

    def __exit__(self, *exc):
        self.left += 1
        return False


@pytest.fixture
def fake_annotation(monkeypatch):
    import jax.profiler
    _FakeAnnotation.made = []
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", _FakeAnnotation)
    return _FakeAnnotation


def test_span_opens_trace_annotation_only_while_enabled(fake_annotation):
    tr = Tracer(enabled=False)
    with tr.span("dispatch", round=3):
        pass
    tr.record("device_execute", 0.0, 1.0)
    tr.instant("mark")
    # disabled: the class is never constructed (nor looked up)
    assert fake_annotation.made == [] and tr._annotation is None
    tr.enable()
    with tr.span("dispatch", round=3):
        with tr.span("gather"):
            pass
    with tr.span("load"):
        pass
    # record() and instant() stay journal-only
    tr.record("device_execute", 0.0, 1.0, round=3)
    tr.instant("mark", round=3)
    made = fake_annotation.made
    assert [(a.name, a.kwargs) for a in made] == [
        ("fed:dispatch", {"round": 3}),
        ("fed:gather", {"round": 3}),     # the inherited tag
        ("fed:load", {})]
    assert all(a.entered == 1 and a.left == 1 for a in made)
    tr.disable()
    with tr.span("dispatch", round=4):
        pass
    assert len(fake_annotation.made) == 3


def test_trace_module_does_not_import_the_profiler_at_import():
    import subprocess
    import sys
    code = ("import sys, importlib.util as u; "
            "s = u.spec_from_file_location('t', sys.argv[1]); "
            "m = u.module_from_spec(s); s.loader.exec_module(m); "
            "m.Tracer(enabled=False).span('x'); "
            "assert 'jax' not in sys.modules, 'jax imported'")
    import commefficient_tpu.telemetry.trace as tmod
    proc = subprocess.run([sys.executable, "-c", code, tmod.__file__],
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr


def _traced_model(tmp_path, name="j.jsonl", **session_kw):
    model, _ = _fed_model()
    jpath = str(tmp_path / name)
    sess = TelemetrySession(journal=RunJournal(jpath), trace=True,
                            **session_kw)
    model.attach_telemetry(sess)
    return model, sess, jpath


def _journal_spans(jpath):
    records, problems = validate_journal(jpath)
    assert problems == []
    return [s for r in records if r["event"] == "trace"
            for s in r["spans"]]


def test_round_span_parents_the_stages(tmp_path):
    model, sess, jpath = _traced_model(tmp_path)
    for batch in _rounds(3):
        model(batch)
    sess.close()
    spans = _journal_spans(jpath)
    rounds = [s for s in spans if s["name"] == "round"]
    assert [s["round"] for s in rounds] == [0, 1, 2]
    for parent in rounds:
        lo, hi = parent["t0"], parent["t0"] + parent["dur"]
        for stage in ("plan", "stage", "dispatch", "collect"):
            (child,) = [s for s in spans if s["name"] == stage
                        and s["round"] == parent["round"]]
            # rounded to the microsecond on both sides
            assert lo - 2e-6 <= child["t0"]
            assert child["t0"] + child["dur"] <= hi + 2e-6
    # tier_motion, the fifth stage, runs only with a tiered store; the
    # host dispatch spans nested under `dispatch` inherit the id too
    assert {s["round"] for s in spans
            if s["name"] == "round_dispatch"} == {0, 1, 2}


def test_device_wait_in_collect_and_in_emit_round(tmp_path):
    model, sess, jpath = _traced_model(tmp_path)
    for batch in _rounds(3):
        model(batch)
    sess.close()
    spans = _journal_spans(jpath)
    waits = [s for s in spans if s["name"] == "device_wait"]
    collects = {s["round"]: s for s in spans if s["name"] == "collect"}
    in_collect = [w for w in waits if "of" not in w]
    in_emit = [w for w in waits if "of" in w]
    # the lagged change-bit read: rounds 1 and 2 read the round before
    assert [w["round"] for w in in_collect] == [1, 2]
    for w in in_collect:
        c = collects[w["round"]]
        assert c["t0"] - 2e-6 <= w["t0"]
        assert w["t0"] + w["dur"] <= c["t0"] + c["dur"] + 2e-6
    # _emit_round materialises round r-1 inside round r's call, and
    # the last round at close (under no round span)
    assert [(w["of"], w.get("round")) for w in in_emit] == [
        (0, 1), (1, 2), (2, None)]


@pytest.mark.parametrize("cohort", [1, 0])
def test_loader_same_batches_and_load_spans_closed_before_yield(
        tmp_path, cohort):
    from commefficient_tpu.data import FedCIFAR10, FedLoader
    from commefficient_tpu.data.transforms import cifar10_transforms
    from commefficient_tpu.telemetry.journal import summarize

    def batches(trace_on):
        ds = FedCIFAR10(str(tmp_path), synthetic_examples=(200, 20))
        # the train transform has a cohort form; the test one (the
        # deterministic one) has none, so its rounds go client by client
        ds.transform = cifar10_transforms()[0 if cohort else 1]
        loader = FedLoader(ds, num_workers=4, local_batch_size=8, seed=7)
        out, open_at_yield = [], []
        if trace_on:
            TRACE.enable()
        for ids, data, mask in loader.epoch():
            open_at_yield.append(list(TRACE._thread_stack()))
            out.append((ids.copy(), [d.copy() for d in data],
                        mask.copy()))
        spans, _ = TRACE.drain()
        TRACE.disable()
        return out, spans, open_at_yield, ds

    off, no_spans, _, _ = batches(False)
    on, spans, open_at_yield, ds = batches(True)
    assert no_spans == [] and len(off) == len(on) > 1
    for (i0, d0, m0), (i1, d1, m1) in zip(off, on):
        np.testing.assert_array_equal(i0, i1)
        np.testing.assert_array_equal(m0, m1)
        for a, b in zip(d0, d1):
            np.testing.assert_array_equal(a, b)
    # every span closes before the yield
    assert all(stack == [] for stack in open_at_yield)
    loads = [s for s in spans if s["name"] == "load"]
    # one `load` a batch, then the one that found the epoch over
    assert [s["seq"] for s in loads] == list(range(len(on) + 1))
    for name in ("load_fetch", "load_assemble"):
        assert len([s for s in spans if s["name"] == name]) == len(on)
    assert len([s for s in spans
                if s["name"] == "load_sample"]) == len(on) + 1
    for parent in loads[:-1]:
        lo, hi = parent["t0"], parent["t0"] + parent["dur"]
        kids = [s for s in spans if s["name"].startswith("load_")
                and lo - 2e-6 <= s["t0"] <= hi + 2e-6]
        # the buffers first, then the fetch that writes into them
        assert [k["name"] for k in kids] == [
            "load_sample", "load_assemble", "load_fetch"]
        assert sum(k["dur"] for k in kids) <= parent["dur"] + 5e-6
    fetch = [s for s in spans if s["name"] == "load_fetch"]
    assert all(s["clients"] == 4 and 0 < s["transform_s"] <= s["dur"]
               + 1e-6 for s in fetch)
    assert [s["cohort"] for s in fetch] == [cohort] * len(on)
    nbytes = sum(d.nbytes for d in on[0][1])
    assert all(s["bytes"] == nbytes for s in spans
               if s["name"] == "load_assemble")
    assert ds.transform_s == pytest.approx(
        sum(s["transform_s"] for s in fetch), abs=1e-5)
    summary = summarize([{"event": "trace", "spans": spans}])
    assert summary["loader_cohort_share"] == float(cohort)
    assert summary["trace_stages"]["load_fetch"]["n"] == len(on)


def test_trace_flush_cadence_injected_clock(tmp_path):
    """`_flush_trace` writes at most once per TRACE_FLUSH_S of the
    session's monotonic clock, earlier once a ring is half full, and
    close() writes what is left."""
    from commefficient_tpu.telemetry import TRACE_FLUSH_S
    t = [100.0]
    jpath = str(tmp_path / "j.jsonl")
    sess = TelemetrySession(journal=RunJournal(jpath), trace=True,
                            clock=lambda: t[0])
    TRACE.ring_size = 8

    def trace_events():
        if not os.path.isfile(jpath):
            return []
        with open(jpath) as f:
            return [json.loads(line) for line in f
                    if '"event": "trace"' in line]

    try:
        with TRACE.span("a"):
            pass
        sess._flush_trace()
        assert trace_events() == []            # no time has passed
        t[0] = 100.0 + 0.9 * TRACE_FLUSH_S
        sess._flush_trace()
        assert trace_events() == []
        t[0] = 100.0 + TRACE_FLUSH_S
        sess._flush_trace()                    # the interval has passed
        assert [len(e["spans"]) for e in trace_events()] == [1]
        for _ in range(3):
            with TRACE.span("b"):
                pass
        sess._flush_trace()
        assert len(trace_events()) == 1        # 3 of 8: under half
        with TRACE.span("b"):
            pass
        sess._flush_trace()                    # 4 of 8: half full
        assert [len(e["spans"]) for e in trace_events()] == [1, 4]
        with TRACE.span("c"):
            pass
        sess._flush_trace()
        assert len(trace_events()) == 2
        sess.close()                           # forces the rest
        assert [len(e["spans"]) for e in trace_events()] == [1, 4, 1]
    finally:
        TRACE.ring_size = 4096
        sess.close()


def test_per_round_trace_fsync_is_gone(tmp_path):
    """Twenty traced rounds inside one flush interval of the session's
    clock write no `trace` event until close."""
    t = [5.0]
    model, sess, jpath = _traced_model(tmp_path, clock=lambda: t[0])
    for batch in _rounds(20):
        model(batch)
        t[0] += 0.01
    with open(jpath) as f:
        assert not any('"event": "trace"' in line for line in f)
    sess.close()
    assert len([s for s in _journal_spans(jpath)
                if s["name"] == "round"]) == 20

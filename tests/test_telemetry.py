"""Telemetry subsystem tests (ISSUE 4): neutrality of the on-device
metric vector (bit-identical ServerState with telemetry on vs off,
zero implicit transfers in a guarded scanned span), journal schema +
invariant validation, span/round metric semantics, compile-event
capture, and bit-exact checkpoint/resume of the per-client throughput
tracker. Plus the satellite units: schema-tolerant TableLogger /
schema-driven TSVLogger and the retry journal hook.
"""
import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from commefficient_tpu.config import Config
from commefficient_tpu.federated.api import FedModel, FedOptimizer
from commefficient_tpu.telemetry import (
    RunJournal, TelemetrySession, parse_profile_spans, tmetrics,
)
from commefficient_tpu.telemetry.clients import ClientThroughputTracker
from commefficient_tpu.telemetry.journal import (
    append_event, validate_journal,
)
from commefficient_tpu.utils.faults import FaultSchedule, InjectedFault

D = 8


def loss_fn(params, batch, mask):
    x, y = batch
    pred = x @ params["w"]
    per_ex = 0.5 * (pred - y) ** 2
    denom = jnp.maximum(mask.sum(), 1.0)
    loss = (per_ex * mask).sum() / denom
    return loss, (loss,)


def _fed_model(telemetry=True, **kw):
    base = dict(mode="uncompressed", grad_size=D, weight_decay=0.0,
                num_workers=8, local_momentum=0.0, virtual_momentum=0.9,
                error_type="none", microbatch_size=-1, num_clients=8,
                telemetry=telemetry)
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


def _session(tmp_path, **kw):
    jpath = str(tmp_path / "journal.jsonl")
    return TelemetrySession(journal=RunJournal(jpath), **kw), jpath


# ---------------- metric vector --------------------------------------------

def test_metric_vector_fixed_shape_and_names():
    assert len(set(tmetrics.METRIC_NAMES)) == tmetrics.NUM_METRICS
    vec = tmetrics.round_vector(
        losses=jnp.ones(8), counts=jnp.full(8, 4.0),
        delta=jnp.asarray(np.r_[1.0, 0.0, 2.0, np.zeros(D - 3)],
                          jnp.float32),
        verror=jnp.zeros(D), vvelocity=jnp.ones(D),
        survivors=jnp.float32(8.0))
    assert vec.shape == (tmetrics.NUM_METRICS,)
    assert vec.dtype == jnp.float32
    named = tmetrics.named(np.asarray(vec))
    assert named["survivors"] == 8.0
    assert named["examples"] == 32.0
    assert named["realized_k"] == 2.0
    assert named["estimate_residual"] == 0.0  # zero error accumulator
    assert tmetrics.named(np.asarray(tmetrics.empty_vector())) == {}


def test_telemetry_default_on():
    # the "permanently on" claim: the default config traces the
    # telemetry-carrying round program
    assert Config().telemetry is True


def test_telemetry_on_off_bit_identical_state():
    """The tentpole neutrality contract: telemetry is pure observation
    — ServerState/ps_weights are BIT-identical with it on or off, on
    both the per-round and scanned paths."""
    finals = []
    for tele_on in (True, False):
        model, _ = _fed_model(telemetry=tele_on)
        stream = _rounds(6)
        # 2 per-round calls, then one scanned span of 4
        for ids, data, mask in stream[:2]:
            model((ids, data, mask))
        span = stream[2:]
        model.run_rounds(
            np.stack([s[0] for s in span]),
            tuple(np.stack([s[1][i] for s in span]) for i in range(2)),
            np.stack([s[2] for s in span]),
            np.full(4, 0.1, np.float32))
        finals.append(model.server)
    a, b = finals
    np.testing.assert_array_equal(np.asarray(a.ps_weights),
                                  np.asarray(b.ps_weights))
    np.testing.assert_array_equal(np.asarray(a.Vvelocity),
                                  np.asarray(b.Vvelocity))
    np.testing.assert_array_equal(np.asarray(a.Verror),
                                  np.asarray(b.Verror))
    assert int(a.round_idx) == int(b.round_idx) == 6


def test_scanned_span_zero_transfers_with_telemetry(tmp_path, sanitize):
    """A guarded steady-state span stays transfer-clean WITH a live
    telemetry session: the span-boundary metric export is an explicit
    device_get, never an implicit transfer."""
    model, _ = _fed_model()
    sess, jpath = _session(tmp_path)
    model.attach_telemetry(sess)
    stream = _rounds(6)

    def span_args(rs):
        return (np.stack([s[0] for s in rs]),
                tuple(np.stack([s[1][i] for s in rs]) for i in range(2)),
                np.stack([s[2] for s in rs]),
                np.full(len(rs), 0.1, np.float32))

    model.run_rounds(*span_args(stream[:3]))  # compile outside guard
    with sanitize.forbid_transfers():
        model.run_rounds(*span_args(stream[3:]))
    sess.close()
    records, problems = validate_journal(jpath)
    assert not problems, problems
    rounds = [r for r in records if r["event"] == "round"]
    assert [r["round"] for r in rounds] == list(range(6))


# ---------------- journal + span semantics ---------------------------------

def test_span_events_and_round_metrics(tmp_path):
    model, _ = _fed_model()
    sess, jpath = _session(tmp_path)
    model.attach_telemetry(sess)
    stream = _rounds(3)
    model.run_rounds(
        np.stack([s[0] for s in stream]),
        tuple(np.stack([s[1][i] for s in stream]) for i in range(2)),
        np.stack([s[2] for s in stream]),
        np.full(3, 0.1, np.float32))
    sess.close(ok=True)
    records, problems = validate_journal(jpath)
    assert not problems, problems
    spans = [r for r in records if r["event"] == "span"]
    assert len(spans) == 1
    assert spans[0]["first_round"] == 0 and spans[0]["rounds"] == 3
    assert spans[0]["dispatch_s"] >= 0 and spans[0]["block_s"] >= 0
    rounds = [r for r in records if r["event"] == "round"]
    assert len(rounds) == 3
    for rec in rounds:
        m = rec["metrics"]
        assert set(m) == set(tmetrics.METRIC_NAMES)
        assert m["survivors"] == 8.0
        assert m["examples"] == 32.0
        assert np.isfinite(m["train_loss"])
    assert records[-1]["event"] == "run_end" and records[-1]["ok"] is True


def test_round_metrics_respect_dropout(tmp_path):
    """Survivor count and processed examples in the metric vector
    reflect the round's ACTUAL survivors, not the sampled count."""
    model, _ = _fed_model()
    model.set_fault_schedule(FaultSchedule(drop_slots={0: [1, 5, 6]}))
    sess, jpath = _session(tmp_path)
    model.attach_telemetry(sess)
    for ids, data, mask in _rounds(2):
        model((ids, data, mask))
    sess.close()
    records, problems = validate_journal(jpath)
    assert not problems, problems
    by_round = {r["round"]: r["metrics"] for r in records
                if r["event"] == "round"}
    assert by_round[0]["survivors"] == 5.0
    assert by_round[0]["examples"] == 20.0  # 5 survivors x 4 examples
    assert by_round[1]["survivors"] == 8.0
    assert by_round[1]["examples"] == 32.0


def test_injected_fault_journaled(tmp_path):
    model, _ = _fed_model()
    model.set_fault_schedule(FaultSchedule(crash_after=1))
    sess, jpath = _session(tmp_path)
    model.attach_telemetry(sess)
    stream = _rounds(3)
    with pytest.raises(InjectedFault):
        for ids, data, mask in stream:
            model((ids, data, mask))
    records, _ = validate_journal(jpath)
    faults = [r for r in records if r["event"] == "injected_fault"]
    assert faults and faults[0]["fault"] == "crash_after"
    assert faults[0]["round"] == 1


def test_compile_events_and_steady_state_warning(tmp_path):
    sess, jpath = _session(tmp_path)
    # a fresh jitted program -> one backend compile -> journaled
    jax.jit(lambda v: v * 2.0 + 1.0)(jnp.arange(3.0)).block_until_ready()
    sess.mark_steady_state()
    jax.jit(lambda v: v * 3.0 - 7.0)(jnp.arange(3.0)).block_until_ready()
    with sess.expect_compiles("legit late compile"):
        jax.jit(lambda v: v / 5.0)(jnp.arange(3.0)).block_until_ready()
    sess.close()
    records, problems = validate_journal(jpath)
    assert not problems, problems
    kinds = [r["event"] for r in records]
    assert "compile" in kinds
    warns = [r for r in records if r["event"] == "compile_warning"]
    assert len(warns) == 1 and warns[0]["unexpected"] is True
    # expect_compiles suppressed the third compile's warning
    assert sum(1 for k in kinds if k == "compile") >= 2


def test_journal_validation_detects_problems(tmp_path):
    jpath = str(tmp_path / "bad.jsonl")
    j = RunJournal(jpath)
    j.event("round", round=0, metrics={"train_loss": 1.0})
    j.event("round", round=1)
    j.event("round", round=1)           # duplicate
    j.event("round", round=0)           # out of order AND duplicate
    with open(jpath, "a") as f:         # torn tail
        f.write('{"v": 1, "event": "round", "ts": 1.0, "ro')
    records, problems = validate_journal(jpath)
    assert len(records) == 4
    assert any("duplicate round 1" in p for p in problems)
    assert any("duplicate round 0" in p for p in problems)
    assert any("torn tail" in p for p in problems)


def test_append_after_torn_tail_seals_fragment(tmp_path):
    """A resume appending to a journal whose last append was torn
    mid-write must not concatenate onto the fragment: the torn line is
    sealed with a newline, stays its own (detectably invalid) line,
    and every committed record before AND after it survives. Once
    sealed it is an INTERIOR corrupt line — skipped-and-counted
    (ISSUE 12), not a validation failure: the resumed journal still
    validates, and the count surfaces through `counters` into
    summarize()."""
    from commefficient_tpu.telemetry.journal import summarize
    jpath = str(tmp_path / "resumed.jsonl")
    append_event(jpath, "round", round=0)
    with open(jpath, "ab") as f:  # simulate a mid-append preemption
        f.write(b'{"v": 1, "event": "round", "ts": 2.0, "ro')
    append_event(jpath, "round", round=1)  # the "resumed" process
    counters = {}
    records, problems = validate_journal(jpath, counters=counters)
    assert [r.get("round") for r in records] == [0, 1]
    assert problems == []  # the sealed fragment is tolerated...
    assert counters["corrupt_interior"] == 1  # ...but counted
    assert counters["corrupt_lines"] == [2]
    summary = summarize(records, corrupt_lines=counters[
        "corrupt_interior"])
    assert summary["corrupt_lines"] == 1


def test_journal_nonfinite_metrics_stay_strict_json(tmp_path):
    """A diverging run's NaN/Inf metrics must journal as STRICT JSON
    (string sentinels), not bare NaN tokens only Python accepts — and
    still validate."""
    jpath = str(tmp_path / "nan.jsonl")
    RunJournal(jpath).event(
        "round", round=0,
        metrics={"train_loss": float("nan"), "update_l2": float("inf"),
                 "error_l2": np.float32("nan"), "survivors": 8.0})
    raw = open(jpath).read()
    assert "NaN" not in raw.replace('"NaN"', "")  # only quoted form
    rec = json.loads(raw)                          # strict round-trip
    assert rec["metrics"]["train_loss"] == "NaN"
    assert rec["metrics"]["update_l2"] == "Infinity"
    assert rec["metrics"]["error_l2"] == "NaN"
    _, problems = validate_journal(jpath)
    assert not problems, problems


def test_session_survives_unserializable_field(tmp_path, capsys):
    sess, jpath = _session(tmp_path)
    sess.journal_event("weird", payload=object())  # json TypeError
    sess.journal_event("fine", n=1)
    sess.close()
    assert "journal write failed" in capsys.readouterr().out
    records, problems = validate_journal(jpath)
    assert not problems, problems
    assert [r["event"] for r in records] == ["fine", "run_end"]


def test_journal_batch_events(tmp_path):
    jpath = str(tmp_path / "batch.jsonl")
    j = RunJournal(jpath)
    j.events([("span", {"first_round": 0, "rounds": 2}),
              ("round", {"round": 0}), ("round", {"round": 1})])
    records, problems = validate_journal(jpath)
    assert not problems, problems
    assert [r["event"] for r in records] == ["span", "round", "round"]


def test_journal_summary_cli(tmp_path):
    spec = importlib.util.spec_from_file_location(
        "journal_summary",
        os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "scripts", "journal_summary.py"))
    js = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(js)

    good = str(tmp_path / "good.jsonl")
    append_event(good, "round", round=0)
    append_event(good, "round", round=1)
    assert js.main([good, "--quiet"]) == 0

    bad = str(tmp_path / "bad.jsonl")
    append_event(bad, "round", round=0)
    append_event(bad, "round", round=0)
    assert js.main([bad, "--quiet"]) == 1
    assert js.main([str(tmp_path / "missing.jsonl")]) == 2


def test_round_comm_bytes_journaled(tmp_path):
    """ISSUE 5 satellite: the accountant's per-round byte totals ride
    the round events (per-round path) and run_end carries the
    cumulative pair — and the whole journal still validates."""
    model, _ = _fed_model()
    sess, jpath = _session(tmp_path)
    model.attach_telemetry(sess)
    for ids, data, mask in _rounds(3):
        model((ids, data, mask))
    sess.close(ok=True)
    records, problems = validate_journal(jpath)
    assert not problems, problems
    rounds = [r for r in records if r["event"] == "round"]
    assert len(rounds) == 3
    for rec in rounds:
        # uncompressed upload: 8 clients x D floats x 4 bytes
        assert rec["up_bytes"] == 8 * D * 4.0
        assert rec["down_bytes"] >= 0
    # round 1's download charges the weights round 0 changed
    assert rounds[1]["down_bytes"] > 0
    end = records[-1]
    assert end["event"] == "run_end"
    assert end["up_bytes_total"] == sum(r["up_bytes"] for r in rounds)
    assert end["down_bytes_total"] == sum(r["down_bytes"]
                                          for r in rounds)


def test_span_comm_bytes_journaled(tmp_path):
    """Scanned path: every round event of a span carries its byte
    totals (the accounting loop feeds on_span's comm_rows)."""
    model, _ = _fed_model()
    sess, jpath = _session(tmp_path)
    model.attach_telemetry(sess)
    stream = _rounds(3)
    model.run_rounds(
        np.stack([s[0] for s in stream]),
        tuple(np.stack([s[1][i] for s in stream]) for i in range(2)),
        np.stack([s[2] for s in stream]),
        np.full(3, 0.1, np.float32))
    sess.close(ok=True)
    records, problems = validate_journal(jpath)
    assert not problems, problems
    rounds = [r for r in records if r["event"] == "round"]
    assert len(rounds) == 3
    assert all(r["up_bytes"] == 8 * D * 4.0 for r in rounds)
    assert records[-1]["up_bytes_total"] == 3 * 8 * D * 4.0


def test_validate_journal_comm_invariants(tmp_path):
    """Byte-total invariants are CHECKED, not just emitted: negative
    or non-numeric totals fail, and a run_end cumulative smaller than
    the segment's per-round sum fails."""
    jpath = str(tmp_path / "comm.jsonl")
    j = RunJournal(jpath)
    j.event("round", round=0, down_bytes=-5.0)
    j.event("round", round=1, up_bytes="many")
    _, problems = validate_journal(jpath)
    assert any("down_bytes" in p for p in problems)
    assert any("up_bytes" in p for p in problems)

    jpath2 = str(tmp_path / "short.jsonl")
    j2 = RunJournal(jpath2)
    j2.event("round", round=0, down_bytes=2.0 * 1024 ** 2,
             up_bytes=50.0)
    j2.event("run_end", down_bytes_total=10.0, up_bytes_total=50.0)
    _, problems = validate_journal(jpath2)
    assert any("down_bytes_total" in p for p in problems)
    assert not any("up_bytes_total" in p for p in problems)

    # summarize surfaces the totals
    from commefficient_tpu.telemetry.journal import summarize
    recs, _ = validate_journal(jpath2)
    assert summarize(recs)["down_mib"] == pytest.approx(2.0)


def test_parse_profile_spans():
    assert parse_profile_spans("") is None
    assert parse_profile_spans("2:4") == (2, 4)
    for bad in ("x:y", "3", "4:2", "-1:2", "2:2"):
        with pytest.raises(ValueError):
            parse_profile_spans(bad)
    valid = dict(mode="uncompressed", error_type="none",
                 local_momentum=0.0, num_clients=8)
    with pytest.raises(ValueError):
        Config(profile_spans="oops", scan_rounds=True,
               **valid).validate()
    # spans only exist on the scanned path: a well-formed spec without
    # --scan_rounds fails loud instead of silently never capturing
    with pytest.raises(ValueError):
        Config(profile_spans="2:4", **valid).validate()
    Config(profile_spans="2:4", scan_rounds=True, **valid).validate()


def test_validate_journal_resets_per_run_segment(tmp_path):
    """A resumed run reusing the same --journal_path replays rounds
    past its last checkpoint: a fresh run_start opens a new segment,
    so cross-segment repeats are history, not violations — while
    in-segment duplicates still fail."""
    jpath = str(tmp_path / "resumed.jsonl")
    j = RunJournal(jpath)
    j.event("run_start", driver="cv_train")
    j.event("round", round=0)
    j.event("round", round=1)
    j.event("round", round=2)           # preempted here, ckpt at 1
    j.event("run_start", driver="cv_train", resumed_round=1)
    j.event("round", round=1)           # healthy replay
    j.event("round", round=2)
    _, problems = validate_journal(jpath)
    assert not problems, problems
    j.event("round", round=2)           # in-segment duplicate: invalid
    _, problems = validate_journal(jpath)
    assert any("duplicate round 2" in p for p in problems)


# ---------------- throughput tracker ---------------------------------------

def test_tracker_ema_and_estimates():
    tr = ClientThroughputTracker(6, ema_decay=0.5)
    # first completed round seeds the EMA with the raw sample
    tr.update_round([0, 1, 2], [10.0, 20.0, 0.0], round_seconds=2.0)
    np.testing.assert_allclose(tr.examples_per_sec([0, 1]),
                               [5.0, 10.0])
    # zero examples: participation only
    assert tr.examples_per_sec([2])[0] == 0.0
    assert list(tr.participation_counts(range(3))) == [1, 1, 1]
    assert list(tr.completion_counts(range(3))) == [1, 1, 0]
    # second observation folds in at decay 0.5
    tr.update_round([0], [30.0], round_seconds=2.0)
    np.testing.assert_allclose(tr.examples_per_sec([0])[0],
                               0.5 * 5.0 + 0.5 * 15.0)
    # deadline estimation: unmeasured clients estimate to +inf
    est = tr.estimate_round_seconds([0, 5], [100.0, 100.0])
    np.testing.assert_allclose(est[0],
                               100.0 / tr.examples_per_sec([0])[0])
    assert np.isinf(est[1])
    # no timing signal -> no state movement
    before = tr.state_dict()
    tr.update_round([0], [10.0], round_seconds=0.0)
    for k, v in tr.state_dict().items():
        np.testing.assert_array_equal(v, before[k])


def test_tracker_checkpoint_roundtrip_bit_exact(ckpt_dir):
    from commefficient_tpu.utils.checkpoint import (
        load_checkpoint, save_checkpoint,
    )
    model, _ = _fed_model()
    # irregular rates from real-ish timings
    model.throughput.update_round(
        np.arange(8), np.linspace(1, 9, 8), round_seconds=0.377)
    model.throughput.update_round(
        np.arange(4), np.linspace(3, 5, 4), round_seconds=0.119)
    path = os.path.join(ckpt_dir, "t")
    save_checkpoint(path, model.server, model.clients,
                    throughput=model.throughput.state_dict(),
                    fingerprint=model.checkpoint_fingerprint)
    ckpt = load_checkpoint(path)
    assert ckpt.throughput is not None
    fresh, _ = _fed_model()
    fresh.load_state(ckpt)
    for k, v in model.throughput.state_dict().items():
        np.testing.assert_array_equal(
            v, fresh.throughput.state_dict()[k], err_msg=k)


def test_crash_resume_preserves_tracker_ema(ckpt_dir, tmp_path):
    """The ISSUE acceptance bit: crash -> resume restores the
    throughput EMA bit-exactly through the rotated-checkpoint path the
    drivers use."""
    from commefficient_tpu.utils.checkpoint import (
        load_latest, save_rotating,
    )
    model, _ = _fed_model()
    sess, _ = _session(tmp_path)
    model.attach_telemetry(sess)
    model.set_fault_schedule(FaultSchedule(crash_after=2))
    prefix = os.path.join(ckpt_dir, "run")
    stream = _rounds(4)
    saved = None
    with pytest.raises(InjectedFault):
        for ids, data, mask in stream:
            model((ids, data, mask))
            # snapshot what THIS save embeds: the resume must restore
            # exactly the last successfully checkpointed state (the
            # crash round's own metrics land after the save, like any
            # work past the final checkpoint, and are lost with it)
            saved = model.throughput.state_dict()
            save_rotating(prefix, model.server, model.clients,
                          fingerprint=model.checkpoint_fingerprint,
                          throughput=saved)
    assert saved is not None
    assert (saved["completions"] > 0).any()  # EMAs actually moved
    resumed, _ = _fed_model()
    ckpt = load_latest(prefix,
                       expect_fingerprint=resumed.checkpoint_fingerprint)
    resumed.load_state(ckpt)
    for k, v in saved.items():
        np.testing.assert_array_equal(
            v, resumed.throughput.state_dict()[k], err_msg=k)


def test_tracker_rejects_wrong_population():
    tr = ClientThroughputTracker(4)
    # sparse rows: a capture naming a client id beyond this run's
    # population is the incompatibility signal (an EMPTY capture is
    # population-agnostic by design — nothing was ever seen)
    other = ClientThroughputTracker(8)
    other.force([7], rate=[1.0])
    with pytest.raises(ValueError):
        tr.load_state_dict(other.state_dict())
    # legacy dense captures still carry the population in their shape
    legacy = {"rate": np.zeros(8, np.float32),
              "participations": np.zeros(8, np.int64),
              "completions": np.zeros(8, np.int64),
              "busy_seconds": np.zeros(8, np.float64)}
    with pytest.raises(ValueError):
        tr.load_state_dict(legacy)


# ---------------- satellite units ------------------------------------------

def test_table_logger_tolerates_schema_drift(capsys):
    from commefficient_tpu.utils.logging import TableLogger
    t = TableLogger()
    t.append({"epoch": 1, "loss": 0.5})
    t.append({"epoch": 2})                       # lost a key: no KeyError
    t.append({"epoch": 3, "loss": 0.4, "acc": 0.9})  # gained a key
    out = capsys.readouterr().out
    assert "acc" in out and out.count("epoch") == 2  # header reprinted
    assert "-" in out                            # missing cell placeholder


def test_tsv_logger_schema_driven():
    from commefficient_tpu.utils.logging import TSVColumn, TSVLogger
    legacy = TSVLogger()
    legacy.append({"epoch": 1, "total_time": 3600.0, "test_acc": 0.5})
    assert str(legacy) == "epoch,hours,top1Accuracy\n1,1.00000000,50.00"
    legacy.append({"epoch": 2})  # missing sources render blank
    assert str(legacy).splitlines()[-1] == "2,,"
    custom = TSVLogger(columns=(
        TSVColumn("round", "round"),
        TSVColumn("ppl", "val_ppl", "{:.1f}")))
    custom.append({"round": 7, "val_ppl": 12.34})
    assert str(custom) == "round,ppl\n7,12.3"


def test_with_retries_on_retry_hook():
    from commefficient_tpu.utils.retry import with_retries
    calls = []
    state = {"n": 0}

    def flaky():
        state["n"] += 1
        if state["n"] < 3:
            raise ConnectionError("transient blip")
        return "ok"

    assert with_retries(flaky, sleep=lambda s: None,
                        on_retry=lambda a, e, d: calls.append((a, d))
                        ) == "ok"
    assert [a for a, _ in calls] == [0, 1]

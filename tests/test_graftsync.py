"""graftsync (ISSUE 14): the static concurrency & durability-ordering
auditor, its registries, and the runtime LockOrderSanitizer.

What is pinned here, in the order the tentpole's claims make it
load-bearing:

  * every rule SY001-SY006 FIRES on a seeded positive control and
    stays QUIET on the matching negative — an auditor whose rules
    stop firing is worse than none (it keeps certifying the tree
    clean);
  * the suppression and baseline machinery have graftlint semantics,
    and the SHIPPED baseline is EMPTY while the tree audits clean —
    the "apply every real finding" satellite, kept honest forever;
  * the SY006 ordering registry covers the four named happens-before
    edges, and deleting any one barrier from a SCRATCH COPY of its
    registered function turns the audit red (fixture source — the
    tree itself is never mutated);
  * the report digest is bit-identical across independent runs, and
    the journaled `sync_audit_digest` event validates;
  * the LockOrderSanitizer catches a scripted ABBA order and stays
    green on consistent orders, RLock re-entrancy, and the real
    bounded-queue writers under deterministic interleaving stress —
    including regression coverage for the two findings this PR fixed
    (the prefetch `_warm` guard, the writer's deferred-failure
    slot).
"""
import ast
import json
import os
import queue
import textwrap
import threading

import pytest

from commefficient_tpu.analysis.domains import (
    ORDERING_EDGES, SHARED_STATE,
)
from commefficient_tpu.analysis.engine import Baseline
from commefficient_tpu.analysis.syncaudit import (
    SYNC_RULE_DOCS, ordering_findings, report_digest, run_sync_audit,
    sync_source,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def codes(src: str, **kw):
    return sorted({v.rule for v in sync_source(
        "snippet.py", textwrap.dedent(src), **kw)})


# ---------------------------------------------------------------------------
# per-rule fixtures: positive (must fire) and negative (must stay quiet)

# SY001 (a): a REGISTERED attribute (Tracer._rings is in
# SHARED_STATE) mutated outside its guard
SY001_POS = """
    import threading

    class Tracer:
        def __init__(self):
            self._lock = threading.Lock()
            self._rings = {}

        def commit(self, ident, rec):
            self._rings.setdefault(ident, []).append(rec)
"""
SY001_NEG = """
    import threading

    class Tracer:
        def __init__(self):
            self._lock = threading.Lock()
            self._rings = {}

        def commit(self, ident, rec):
            with self._lock:
                self._rings.setdefault(ident, []).append(rec)
"""

SY002_POS = """
    import threading
    lock_a = threading.Lock()
    lock_b = threading.Lock()

    def forward():
        with lock_a:
            with lock_b:
                pass

    def backward():
        with lock_b:
            with lock_a:
                pass
"""
SY002_NEG = """
    import threading
    lock_a = threading.Lock()
    lock_b = threading.Lock()

    def forward():
        with lock_a:
            with lock_b:
                pass

    def also_forward():
        with lock_a:
            with lock_b:
                pass
"""

SY003_POS = """
    def emit(q):
        rec = {"event": "round"}
        q.put(rec)
        rec["late"] = True  # the drain loop may be serializing rec NOW
"""
SY003_NEG = """
    import json

    def emit(q):
        rec = {"event": "round"}
        line = json.dumps(rec)   # serialize producer-side...
        q.put(line)              # ...the queue owns an immutable str
        rec["late"] = True       # the local dict was never enqueued

    def emit_rebound(q):
        rec = {"event": "round"}
        q.put(rec)
        rec = {"event": "next"}  # rebind releases ownership tracking
        rec["fresh"] = True
"""

SY004_POS = """
    import os, threading

    class Writer:
        def __init__(self):
            self._lock = threading.Lock()

        def save(self, fd):
            with self._lock:
                os.fsync(fd)  # a dead NFS mount hangs every lock user
"""
SY004_NEG = """
    import os, threading

    class Writer:
        def __init__(self):
            self._lock = threading.Lock()

        def save(self, fd, tail, rows):
            with self._lock:
                tail.put(rows)  # not a queue: an in-memory table write
            os.fsync(fd)        # the blocking work is OUTSIDE the lock

        def drain(self, q):
            with q.all_tasks_done:
                q.all_tasks_done.wait(1.0)  # the Condition idiom
"""

SY005_POS = """
    import threading

    class Writer:
        def start(self):
            self._thread = threading.Thread(target=self._run,
                                            name="w", daemon=True)
            self._thread.start()

        def _run(self):
            pass
"""
SY005_NEG = """
    import threading

    class Writer:
        def start(self):
            self._thread = threading.Thread(target=self._run,
                                            name="w", daemon=True)
            self._thread.start()

        def _run(self):
            pass

        def close(self):
            self._thread.join()
"""

_SY006_EDGES = {
    "demo-drain-before-read": {
        "path": "snippet.py", "function": "save",
        "before": "flush", "after": "get_many",
        "why": "the tail must be authoritative before the payload "
               "reads it",
    },
}
SY006_POS = """
    class Store:
        def save(self):
            rows = self.tail.get_many([1, 2])  # reads a stale tail
            self.flush()                       # ...barrier AFTER use
            return rows
"""
SY006_NEG = """
    class Store:
        def save(self):
            self.flush()
            return self.tail.get_many([1, 2])
"""

FIXTURES = {
    "SY001": (SY001_POS, SY001_NEG, {}),
    "SY002": (SY002_POS, SY002_NEG, {}),
    "SY003": (SY003_POS, SY003_NEG, {}),
    "SY004": (SY004_POS, SY004_NEG, {}),
    "SY005": (SY005_POS, SY005_NEG, {}),
    "SY006": (SY006_POS, SY006_NEG, {"edges": _SY006_EDGES}),
}


@pytest.mark.parametrize("rule", sorted(SYNC_RULE_DOCS))
def test_rule_fires_on_positive_fixture(rule):
    pos, _, kw = FIXTURES[rule]
    assert rule in codes(pos, **kw), \
        f"{rule} failed to fire on its positive control"


@pytest.mark.parametrize("rule", sorted(SYNC_RULE_DOCS))
def test_rule_quiet_on_negative_fixture(rule):
    _, neg, kw = FIXTURES[rule]
    assert rule not in codes(neg, **kw), f"{rule} false-positived"


def test_every_rule_documented():
    assert sorted(SYNC_RULE_DOCS) == [f"SY00{i}" for i in range(1, 7)]
    assert all(doc for doc in SYNC_RULE_DOCS.values())


# ---------------------------------------------------------------------------
# rule-shape details worth pinning individually


def test_sy001_unregistered_cross_thread_state_must_register():
    """An attribute mutated both from a Thread target and from the
    caller side that is NOT in SHARED_STATE errors at every live
    mutation site — the registry is load-bearing, not advisory."""
    src = """
        import threading

        class Counter:
            def __init__(self):
                self.hits = 0
                self._thread = threading.Thread(target=self._run,
                                                name="c")

            def _run(self):
                self.hits += 1

            def close(self):
                self.hits = 0
                self._thread.join()
    """
    vs = [v for v in sync_source("snippet.py", textwrap.dedent(src))
          if v.rule == "SY001"]
    assert len(vs) == 2  # both live mutation sites, not __init__
    assert all("not in the shared-state registry" in v.message
               for v in vs)


def test_sy001_init_mutations_are_construction():
    """__init__ precedes concurrency: allocating registered state
    there needs no guard (every writer does exactly this)."""
    src = """
        import threading

        class Tracer:
            def __init__(self):
                self._lock = threading.Lock()
                self._rings = {}
                self._dropped = 0
    """
    assert codes(src) == []


def test_sy001_submit_closure_is_a_thread_domain():
    """A closure handed to a writer's .submit() runs on the drain
    thread — its mutations count as thread-side (how the spill
    writer's commit() reaches the tail)."""
    src = """
        class Store:
            def __init__(self, writer):
                self.tally = {}
                self._writer = writer

            def spill(self, ids):
                def commit():
                    self.tally["n"] = len(ids)
                self._writer.submit(commit)

            def read(self):
                self.tally["m"] = 0
                return self.tally
    """
    vs = [v for v in sync_source("snippet.py", textwrap.dedent(src))
          if v.rule == "SY001"]
    assert vs, "submit() closure mutations must count as thread-side"


def test_sy002_cycle_message_names_every_edge_site():
    vs = [v for v in sync_source("snippet.py",
                                 textwrap.dedent(SY002_POS))
          if v.rule == "SY002"]
    assert len(vs) == 1
    assert "lock_a" in vs[0].message and "lock_b" in vs[0].message
    assert "snippet.py:" in vs[0].message


def test_sy002_rlock_reentrancy_is_not_an_edge():
    src = """
        import threading

        class R:
            def __init__(self):
                self._lock = threading.RLock()

            def outer(self):
                with self._lock:
                    self.inner()

            def inner(self):
                with self._lock:
                    pass
    """
    assert codes(src) == []


def test_sy004_acquire_of_second_lock_flagged_not_cv_idiom():
    src = """
        import threading

        class W:
            def __init__(self):
                self._lock = threading.Lock()
                self._other_lock = threading.Lock()

            def bad(self):
                with self._lock:
                    self._other_lock.acquire()
    """
    assert "SY004" in codes(src)


def test_sy005_unbound_thread_is_flagged():
    src = """
        import threading

        def fire_and_forget(job):
            threading.Thread(target=job, name="oneshot").start()
    """
    assert "SY005" in codes(src)


# ---------------------------------------------------------------------------
# suppression + baseline semantics


def test_per_line_suppression_silences_rule():
    src = """
        import os, threading

        class Writer:
            def __init__(self):
                self._lock = threading.Lock()

            def save(self, fd):
                with self._lock:
                    os.fsync(fd)  # graftsync: disable=SY004 -- single-threaded in tests
    """
    assert "SY004" not in codes(src)


def test_suppression_is_rule_specific():
    src = """
        import os, threading

        class Writer:
            def __init__(self):
                self._lock = threading.Lock()

            def save(self, fd):
                with self._lock:
                    os.fsync(fd)  # graftsync: disable=SY001 -- wrong rule
    """
    assert "SY004" in codes(src)


def test_baseline_grandfathers_and_reports_stale(tmp_path):
    vs = sync_source("snippet.py", textwrap.dedent(SY004_POS))
    assert vs
    baseline = Baseline.from_violations(vs)
    new, stale = baseline.apply(vs)
    assert new == [] and stale == []
    # the tree improved: the baseline must shrink deliberately
    new, stale = baseline.apply([])
    assert new == [] and len(stale) == 1
    assert "stale baseline" in stale[0]


def test_shipped_baseline_is_empty_and_tree_is_clean():
    """The acceptance gate: graftsync exits 0 on the tree with an
    EMPTY committed baseline — every real finding was applied or
    suppressed-with-justification, none grandfathered."""
    with open(os.path.join(REPO, "graftsync.baseline.json")) as f:
        shipped = json.load(f)
    assert shipped["entries"] == []
    cwd = os.getcwd()
    os.chdir(REPO)
    try:
        report, findings = run_sync_audit([
            "commefficient_tpu/telemetry", "commefficient_tpu/utils",
            "commefficient_tpu/federated", "commefficient_tpu/parallel",
            "commefficient_tpu/training"])
    finally:
        os.chdir(cwd)
    assert findings == [], [v.render() for v in findings]
    assert report["rules"] == {r: 0 for r in SYNC_RULE_DOCS}


def test_digest_deterministic_across_independent_runs():
    cwd = os.getcwd()
    os.chdir(REPO)
    try:
        r1, _ = run_sync_audit(["commefficient_tpu/telemetry",
                                "commefficient_tpu/federated"])
        r2, _ = run_sync_audit(["commefficient_tpu/telemetry",
                                "commefficient_tpu/federated"])
    finally:
        os.chdir(cwd)
    assert r1["digest"] == r2["digest"]
    assert len(r1["digest"]) == 64
    assert r1["digest"] == report_digest(r1)


def test_journaled_sync_digest_validates(tmp_path):
    from commefficient_tpu.analysis.syncaudit import journal_digest
    from commefficient_tpu.telemetry.journal import validate_journal
    cwd = os.getcwd()
    os.chdir(REPO)
    try:
        report, findings = run_sync_audit(
            ["commefficient_tpu/telemetry"])
    finally:
        os.chdir(cwd)
    path = str(tmp_path / "journal.jsonl")
    journal_digest(path, report, len(findings))
    records, problems = validate_journal(path)
    assert problems == []
    assert records[0]["event"] == "sync_audit_digest"
    assert records[0]["digest"] == report["digest"]
    # and the validator actually checks: corrupt the digest
    rec = dict(records[0])
    rec["digest"] = "short"
    with open(path, "w") as f:
        f.write(json.dumps(rec) + "\n")
    _, problems = validate_journal(path)
    assert any("64-char" in p for p in problems)


# ---------------------------------------------------------------------------
# SY006: the shipped ordering registry


def test_ordering_registry_covers_the_four_named_edges():
    """The four contracts ISSUE 14 names, by frozen registry name —
    a rename or removal here must be a deliberate test edit."""
    for name in ("wal-flush-before-dispatch",
                 "spill-drain-before-checkpoint-payload",
                 "writer-drain-before-save-final",
                 "gather-barrier-before-donated-scatter"):
        assert name in ORDERING_EDGES, name
    assert len(ORDERING_EDGES) >= 4


def _registered_source(edge):
    with open(os.path.join(REPO, edge["path"])) as f:
        return f.read()


def _delete_barrier(source: str, edge) -> str:
    """A SCRATCH copy of the registered file with every line calling
    `edge['before']` inside the registered function replaced by
    `pass` (same indent, so the copy still parses)."""
    tree = ast.parse(source)
    fn = next(n for n in ast.walk(tree)
              if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))
              and n.name == edge["function"])
    lines = source.splitlines(keepends=True)
    needle = edge["before"] + "("
    hit = False
    for i in range(fn.lineno - 1, fn.end_lineno):
        if needle in lines[i]:
            indent = lines[i][:len(lines[i]) - len(lines[i].lstrip())]
            lines[i] = indent + "pass\n"
            hit = True
    assert hit, (f"fixture rot: `{edge['before']}(` not found inside "
                 f"{edge['path']}:{edge['function']}")
    return "".join(lines)


@pytest.mark.parametrize("name", sorted(ORDERING_EDGES))
def test_registered_functions_currently_satisfy_their_edges(name):
    edge = ORDERING_EDGES[name]
    source = _registered_source(edge)
    findings = ordering_findings(
        {edge["path"]: (source, ast.parse(source))}, {name: edge})
    assert findings == [], [v.render() for v in findings]


@pytest.mark.parametrize("name", sorted(ORDERING_EDGES))
def test_deleting_any_barrier_turns_the_audit_red(name):
    """The acceptance gate: delete one barrier in a scratch copy of
    its registered function and SY006 must fire — demonstrated on
    fixture source, never by mutating the tree."""
    edge = ORDERING_EDGES[name]
    mutated = _delete_barrier(_registered_source(edge), edge)
    findings = ordering_findings(
        {edge["path"]: (mutated, ast.parse(mutated))}, {name: edge})
    assert any(v.rule == "SY006" for v in findings), \
        f"deleting `{edge['before']}` did not turn `{name}` red"
    assert any(name in v.message for v in findings)


def test_sy006_barrier_hidden_in_nested_closure_is_red():
    """A barrier moved into a nested def (called conditionally, or
    never) does not dominate anything at runtime — SY006 must not
    count it (review fix: the scan prunes nested function bodies,
    like SY003)."""
    src = textwrap.dedent("""
        class S:
            def save(self):
                def maybe_flush():
                    self.flush()   # only runs if someone calls it
                return self.tail.get_many([1, 2])
    """)
    findings = ordering_findings(
        {"snippet.py": (src, ast.parse(src))}, _SY006_EDGES)
    assert any(v.rule == "SY006" and "GONE" in v.message
               for v in findings)


def test_sy005_annotated_binding_with_join_is_quiet():
    """`self._thread: threading.Thread = Thread(...)` is a binding
    too (review fix: AnnAssign handled alongside Assign)."""
    src = """
        import threading

        class Writer:
            def start(self):
                self._thread: threading.Thread = threading.Thread(
                    target=self._run, name="w")
                self._thread.start()

            def close(self):
                self._thread.join()
    """
    assert "SY005" not in codes(src)


def test_sy006_missing_function_is_red():
    src = "def unrelated():\n    pass\n"
    findings = ordering_findings(
        {"snippet.py": (src, ast.parse(src))},
        {"demo": {"path": "snippet.py", "function": "save",
                  "before": "flush", "after": "get_many",
                  "why": "demo"}})
    assert [v.rule for v in findings] == ["SY006"]
    assert "no longer exists" in findings[0].message


def test_sy006_missing_guarded_call_is_red():
    """Dropping the AFTER call (the guarded operation moved) is an
    error too — the edge must move with it, never rot around it."""
    src = "class S:\n    def save(self):\n        self.flush()\n"
    findings = ordering_findings(
        {"snippet.py": (src, ast.parse(src))}, _SY006_EDGES)
    assert [v.rule for v in findings] == ["SY006"]
    assert "no longer calls" in findings[0].message


# ---------------------------------------------------------------------------
# shared-state registry shape


def test_shared_state_registry_entries_resolve():
    """Every registered Class.attr and its guard must exist in the
    tree (a stale registry entry silently enforces nothing)."""
    classes = {}
    for root, _, files in os.walk(
            os.path.join(REPO, "commefficient_tpu")):
        for fname in files:
            if not fname.endswith(".py"):
                continue
            with open(os.path.join(root, fname)) as f:
                try:
                    tree = ast.parse(f.read())
                except SyntaxError:
                    continue
            for node in ast.walk(tree):
                if isinstance(node, ast.ClassDef):
                    attrs = {n.attr for n in ast.walk(node)
                             if isinstance(n, ast.Attribute)
                             and isinstance(n.value, ast.Name)
                             and n.value.id == "self"}
                    classes.setdefault(node.name, set()).update(attrs)
    for key, guard in SHARED_STATE.items():
        cls, attr = key.split(".")
        assert cls in classes, f"SHARED_STATE names unknown class {cls}"
        assert attr in classes[cls], f"{key} names a missing attribute"
        assert guard in classes[cls], \
            f"{key}: guard {guard} is not an attribute of {cls}"


# ---------------------------------------------------------------------------
# LockOrderSanitizer: the runtime twin


def test_lock_sanitizer_catches_scripted_abba():
    """The positive control the acceptance criteria name: two threads
    take two instrumented locks in opposite orders (sequentially, so
    the test never actually deadlocks) and teardown must raise."""
    from commefficient_tpu.analysis.runtime import (
        LockOrderError, LockOrderSanitizer,
    )
    san = LockOrderSanitizer()
    san.install()
    try:
        lock_a, lock_b = threading.Lock(), threading.Lock()

        def forward():
            with lock_a:
                with lock_b:
                    pass

        def backward():
            with lock_b:
                with lock_a:
                    pass

        t1 = threading.Thread(target=forward, name="abba-fwd")
        t1.start()
        t1.join()
        t2 = threading.Thread(target=backward, name="abba-bwd")
        t2.start()
        t2.join()
    finally:
        san.uninstall()
    with pytest.raises(LockOrderError) as err:
        san.assert_acyclic()
    assert "opposite orders" in str(err.value)


def test_lock_sanitizer_green_on_consistent_order(lock_sanitizer):
    """Consistent A->B nesting from two threads is fine — and the
    fixture form works (teardown asserts acyclic)."""
    lock_a, lock_b = threading.Lock(), threading.Lock()

    def forward():
        with lock_a:
            with lock_b:
                pass

    forward()
    t = threading.Thread(target=forward, name="fwd")
    t.start()
    t.join()
    assert lock_sanitizer.find_cycle() is None


def test_lock_sanitizer_rlock_reentrancy_no_self_edge(lock_sanitizer):
    r = threading.RLock()
    with r:
        with r:
            pass
    assert lock_sanitizer.edges() == {}


def test_lock_sanitizer_uninstall_restores_factories():
    from commefficient_tpu.analysis.runtime import LockOrderSanitizer
    orig_lock, orig_rlock = threading.Lock, threading.RLock
    san = LockOrderSanitizer()
    san.install()
    assert threading.Lock is not orig_lock
    san.uninstall()
    assert threading.Lock is orig_lock
    assert threading.RLock is orig_rlock
    san.uninstall()  # idempotent


def test_real_writers_green_under_sanitizer_and_stress(tmp_path):
    """The armed configuration (CCTPU_SYNC_SANITIZE=1): the async journal writer
    and the checkpoint writer driven from two producer threads under
    the LockOrderSanitizer + deterministic queue-handoff stress.
    Green means: no lock-order cycle, every record durable, FIFO
    drain intact. Also the regression home for this PR's applied
    findings — the writers are constructed INSIDE the instrumented
    scope, so their locks (including the new `_exc_lock`) are all
    recorded."""
    from commefficient_tpu.analysis.runtime import (
        LockOrderSanitizer, interleaving_stress,
    )
    san = LockOrderSanitizer()
    san.install()
    try:
        with interleaving_stress(delay=0.0002):
            from commefficient_tpu.telemetry.journal import (
                RunJournal, validate_journal,
            )
            from commefficient_tpu.utils.checkpoint import (
                AsyncCheckpointWriter,
            )
            jpath = str(tmp_path / "journal.jsonl")
            journal = RunJournal(jpath, async_writer=True)
            writer = AsyncCheckpointWriter(name="test-ckpt")
            done = []

            def produce(lo):
                for i in range(lo, lo + 8):
                    journal.event("checkpoint", path=f"c{i}",
                                  seconds=0.0)
                    writer.submit(lambda i=i: done.append(i))

            t1 = threading.Thread(target=produce, args=(0,),
                                  name="prod-a")
            t2 = threading.Thread(target=produce, args=(100,),
                                  name="prod-b")
            t1.start()
            t2.start()
            t1.join()
            t2.join()
            writer.drain()
            journal.close()
            writer.close()
    finally:
        san.uninstall()
    san.assert_acyclic()
    assert sorted(done) == list(range(0, 8)) + list(range(100, 108))
    records, problems = validate_journal(jpath)
    assert problems == []
    assert len(records) == 16


def test_async_writer_failure_survives_concurrent_drain():
    """Regression for the applied SY001 finding: the deferred-failure
    slot is now guarded (`_exc_lock`), so a failure stored by the
    writer thread is never lost to a concurrent caller-side clear —
    the submitted error MUST surface at drain()/close(), stress or
    not."""
    from commefficient_tpu.analysis.runtime import interleaving_stress
    from commefficient_tpu.utils.checkpoint import AsyncCheckpointWriter

    class Boom(RuntimeError):
        pass

    with interleaving_stress(delay=0.0002):
        writer = AsyncCheckpointWriter(name="boom")

        def fail():
            raise Boom("spill write failed")

        writer.submit(fail)
        # drain() joins the queue, so the job has run by the time the
        # deferred slot is checked: the failure must surface HERE
        with pytest.raises(Boom):
            writer.drain()
        # the slot was consumed exactly once — close() is clean
        writer.close()


def test_interleaving_stress_restores_queue_methods():
    from commefficient_tpu.analysis.runtime import interleaving_stress
    orig_put, orig_get = queue.Queue.put, queue.Queue.get
    with interleaving_stress():
        assert queue.Queue.put is not orig_put
        q = queue.Queue()
        q.put(1)
        assert q.get() == 1
    assert queue.Queue.put is orig_put
    assert queue.Queue.get is orig_get


def test_statestore_prefetch_guard_is_static_clean():
    """Regression for the applied SY001 findings in
    federated/statestore.py: the prefetch cache writes and the trim
    loop now hold the store lock — pinned by auditing the REAL file
    (a revert re-fires SY001 here, not just in CI's tree pass)."""
    path = os.path.join(REPO, "commefficient_tpu", "federated",
                        "statestore.py")
    with open(path) as f:
        source = f.read()
    findings = sync_source(
        "commefficient_tpu/federated/statestore.py", source)
    assert findings == [], [v.render() for v in findings]

"""graftlint: one failing (positive) and one passing (negative)
fixture snippet per rule GL001-GL006, the suppression/baseline
machinery, and positive controls for the runtime sanitizers — so the
enforcement layer itself can't silently rot (a lint whose rules stop
firing is worse than no lint: it keeps certifying the tree clean)."""
import json
import os
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from commefficient_tpu.analysis.engine import (
    Baseline, LintError, Violation, lint_paths, lint_source,
)
from commefficient_tpu.analysis.rules import ALL_RULES, RULE_DOCS

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def codes(src: str):
    return sorted({v.rule for v in lint_source("snippet.py",
                                               textwrap.dedent(src))})


# ---------------------------------------------------------------------------
# per-rule fixtures: positive (must fire) and negative (must stay quiet)

GL001_POS = """
    import time, jax

    @jax.jit
    def f(x):
        return x * time.time()
"""
GL001_NEG = """
    import time, jax

    def host_timer():
        # wall-clock timing OUTSIDE traced code is legal (drivers'
        # epoch timing, checkpoint age GC)
        return time.time()

    @jax.jit
    def f(x):
        return x * 2.0
"""

GL002_POS = """
    import numpy as np, jax

    @jax.jit
    def f(x):
        return np.asarray(x).sum()
"""
GL002_NEG = """
    import jax, jax.numpy as jnp

    @jax.jit
    def f(x):
        return jnp.asarray(x).sum()
"""

GL003_POS = """
    import jax

    def f():
        key = jax.random.PRNGKey(0)
        a = jax.random.normal(key, (3,))
        b = jax.random.uniform(key, (3,))
        return a + b
"""
GL003_NEG = """
    import jax

    def f():
        key = jax.random.PRNGKey(0)
        k1, k2 = jax.random.split(key)
        a = jax.random.normal(k1, (3,))
        b = jax.random.uniform(k2, (3,))
        return a + b
"""

GL004_POS = """
    import jax, jax.numpy as jnp

    @jax.jit
    def f(x):
        if jnp.any(x > 0):
            return x
        return -x
"""
GL004_NEG = """
    import jax, jax.numpy as jnp

    @jax.jit
    def f(x, mode: str = "abs"):
        # static (trace-time) Python branching over config is legal —
        # it's how round.py selects its three programs
        if mode == "abs":
            return jnp.abs(x)
        return jax.lax.cond(True, lambda v: v, lambda v: -v, x)
"""

GL005_POS = """
    def f():
        try:
            g()
        except Exception:
            return None
"""
GL005_NEG = """
    def f():
        try:
            g()
        except (OSError, ValueError):
            return None

    def h():
        try:
            g()
        except Exception:
            cleanup()
            raise
"""

GL006_POS = """
    def save(path, text):
        with open(path, "w") as f:
            f.write(text)
"""
GL006_NEG = """
    import os

    def save(path, text):
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            f.write(text)
        os.replace(tmp, path)

    def read(path):
        with open(path) as f:
            return f.read()
"""

GL007_POS = """
    import jax
    from jax.experimental.shard_map import shard_map

    def build(f, mesh, P):
        mapped = shard_map(f, mesh=mesh, in_specs=(P("clients"),))
        jitted = jax.experimental.pjit.pjit(f)
        return mapped, jitted
"""
GL007_NEG = """
    import jax
    from jax.experimental.shard_map import shard_map

    def build(f, mesh, P, specs, **extra):
        mapped = shard_map(f, mesh=mesh, in_specs=(P("clients"),),
                           out_specs=P("clients"))
        jitted = jax.experimental.pjit.pjit(
            f, out_shardings=specs)
        # **kwargs forwarding may carry the spec — precision over
        # recall, stay quiet
        fwd = shard_map(f, mesh=mesh, **extra)
        # legal POSITIONAL forms pin the out-spec slot too
        pos = shard_map(f, mesh, (P("clients"),), P("clients"))
        pos_jit = jax.experimental.pjit.pjit(f, specs, specs)
        return mapped, jitted, fwd, pos, pos_jit
"""

GL008_POS = """
    import jax
    from jax import lax

    @jax.jit
    def decode(est):
        vals, idx = lax.top_k(est, 50000)
        also = jax.lax.top_k(est * est, k=65536)
        return vals, idx, also
"""
GL008_NEG = """
    import jax
    from jax import lax

    @jax.jit
    def decode(est, k):
        small = lax.top_k(est, 16)                 # small static k: fine
        approx = jax.lax.approx_max_k(est, 50000)  # the blessed route
        dyn = lax.top_k(est, k)                    # non-constant k: invisible
        other = est.top_k(50000)                   # not jax.lax's
        return small, approx, dyn, other

    def host_side(est):
        # outside traced code: not this rule's business
        return lax.top_k(est, 50000)
"""

GL009_POS = """
    import jax
    import numpy as np

    def survivors(seed, round_idx, n):
        rng = np.random.default_rng(
            np.random.SeedSequence([seed, 0xBEEF1, round_idx]))
        return rng.random(n)

    @jax.jit
    def round_key(key):
        return jax.random.fold_in(key, 0xD00D)
"""
GL009_NEG = """
    import jax
    import numpy as np
    from commefficient_tpu.analysis.domains import DOMAINS

    def survivors(seed, round_idx, n):
        # registry-routed tags are the sanctioned form
        rng = np.random.default_rng(
            np.random.SeedSequence([seed, DOMAINS["dropout"],
                                    round_idx]))
        return rng.random(n)

    @jax.jit
    def round_key(key, i):
        # decimal per-round counters (round indices, worker slots) are
        # stream POSITIONS, not domain tags — out of scope
        return jax.random.fold_in(key, 7), jax.random.fold_in(key, i)
"""

GL010_POS = """
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    def make_mesh(devices):
        # "cleints" is the typo class the registry exists to catch
        return Mesh(np.asarray(devices), axis_names=("cleints",))

    def spec_for(mesh):
        return NamedSharding(mesh, P("batch", None))
"""
GL010_NEG = """
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from commefficient_tpu.analysis.domains import CLIENTS_AXIS

    def make_mesh(devices):
        # registry constants and registry-VALUED literals are both
        # clean (the rule checks by value)
        return Mesh(np.asarray(devices), axis_names=(CLIENTS_AXIS,))

    def spec_for(mesh):
        return NamedSharding(mesh, P("clients", "model"))

    def device_label(x):
        # non-axis strings outside sharding sinks are out of scope
        return str(x) + "tpu:0"
"""

GL011_POS = """
    import time

    def step_time():
        t0 = time.time()
        do_work()
        # both operands wall-clock-derived: an NTP step mid-interval
        # makes this negative or wildly wrong
        return time.time() - t0
"""
GL011_NEG = """
    import os, time

    def step_time(t0):
        # monotonic deltas ARE durations
        return time.monotonic() - t0

    def checkpoint_age(path):
        # wall clock vs an EXTERNAL wall-clock value (file mtime):
        # legitimately wall-clock, not a flagged delta
        return time.time() - os.path.getmtime(path)

    def timestamp():
        # a bare reading (no subtraction) is a timestamp, not a
        # duration
        return time.time()
"""

GL012_POS = """
    import threading

    class Writer:
        def start(self):
            # anonymous: Perfetto rows keyed by Thread-N break across
            # restarts
            self._thread = threading.Thread(target=self._run,
                                            daemon=True)
            self._thread.start()
"""
GL012_NEG = """
    import threading

    class Writer:
        def start(self, **extra):
            self._thread = threading.Thread(target=self._run,
                                            name="journal-writer",
                                            daemon=True)
            self._thread.start()

        def start_forwarded(self, kwargs):
            # **kwargs forwarding: the name may ride there
            return threading.Thread(target=self._run, **kwargs)

        def start_positional(self):
            # Thread(group, target, name): the third positional slot
            # IS the name
            return threading.Thread(None, self._run, "journal-writer")
"""

GL013_POS = """
    import jax
    import jax.numpy as jnp

    @jax.jit
    def admit(weight, target):
        # non-zero float literal: one ulp of drift flips it
        exact = weight == 0.95
        # computed-vs-computed: couples logic to reduction order
        matched = jnp.sum(weight) != target
        return exact, matched
"""
GL013_NEG = """
    import jax
    import jax.numpy as jnp

    @jax.jit
    def sparsity(update, vals, idx, d):
        # exact-zero bit tests: the sanctioned sparsity/sentinel
        # idiom (error-feedback masking, unfilled-slot sentinels)
        realized = jnp.sum(update != 0)
        slots = jnp.where(vals == 0.0, d, idx)
        return realized, slots

    @jax.jit
    def labels_match(preds, labels, ignore):
        # bare-name / int comparisons (ids, label indices) are out
        # of scope for an AST heuristic
        return (preds == labels) & (labels != ignore)
"""

GL014_POS = """
    from commefficient_tpu.control.base import Controller

    class RogueController(Controller):
        NAME = "rogue"
        # claims a plan wire field the CONTROL_FIELDS registry has
        # never heard of — bypasses the uniqueness assert
        WIRE_FIELD = "rogue_knob"

        def plan_value(self):
            return 1.0

        def install(self, value):
            pass
"""
GL014_NEG = """
    from commefficient_tpu.control.base import Controller

    class PoliteController(Controller):
        NAME = "speed_match"
        # a registered CONTROL_FIELDS value is the sanctioned idiom
        WIRE_FIELD = "speed_ratio"

        def plan_value(self):
            return 0.5

        def install(self, value):
            pass

    class AbstractBase(Controller):
        # the base-class empty sentinel is not a field claim
        WIRE_FIELD = ""
"""

GL015_POS = """
    import jax.numpy as jnp

    def sample(v, stride):
        sq = v * v
        return sq[::stride]

    def columns(m, a, b):
        return m[:, a:b:2]
"""
GL015_NEG = """
    import jax

    def sample(v, stride):
        # the strided slice instruction itself
        return jax.lax.slice(v, (0,), v.shape, (stride,))

    def windows(m, off, n):
        # no step, a step of 1 (no op) and a reversal (`rev`)
        return m[:, off:off + n], m[::1], m[::-1]
"""

# rule -> (positive, negative[, lint path]); GL010 and GL015 are
# path-scoped (to the packages that construct shardings; to the traced
# packages), so their fixtures lint under a parallel/ and an ops/ path
# (everything else uses the default snippet.py)
FIXTURES = {
    "GL001": (GL001_POS, GL001_NEG),
    "GL002": (GL002_POS, GL002_NEG),
    "GL003": (GL003_POS, GL003_NEG),
    "GL004": (GL004_POS, GL004_NEG),
    "GL005": (GL005_POS, GL005_NEG),
    "GL006": (GL006_POS, GL006_NEG),
    "GL007": (GL007_POS, GL007_NEG),
    "GL008": (GL008_POS, GL008_NEG),
    "GL009": (GL009_POS, GL009_NEG),
    "GL010": (GL010_POS, GL010_NEG,
              "commefficient_tpu/parallel/snippet.py"),
    "GL011": (GL011_POS, GL011_NEG),
    "GL012": (GL012_POS, GL012_NEG),
    "GL013": (GL013_POS, GL013_NEG),
    "GL014": (GL014_POS, GL014_NEG),
    "GL015": (GL015_POS, GL015_NEG,
              "commefficient_tpu/ops/snippet.py"),
}


def test_gl009_registry_collision_is_flagged():
    """A duplicate tag VALUE inside the registry dict itself is a
    GL009 hit — but only when linting the registry file's path (the
    pure-AST twin of the import-time uniqueness assert)."""
    src = """
        DOMAINS = {
            "dropout": 0x0D120,
            "straggler": 0x51044,
            "sampler": 0x0D120,
        }
    """
    vs = lint_source("commefficient_tpu/analysis/domains.py",
                     textwrap.dedent(src))
    assert [v.rule for v in vs] == ["GL009"]
    assert "collision" in vs[0].message
    # same source under any other path: a plain dict of hex ints is
    # nobody's registry
    assert codes(src) == []


def test_gl009_shipped_registry_is_unique():
    from commefficient_tpu.analysis.domains import DOMAINS
    assert len(set(DOMAINS.values())) == len(DOMAINS)
    # the three historical streams kept their frozen tags
    assert DOMAINS["dropout"] == 0x0D120
    assert DOMAINS["straggler"] == 0x51044
    assert DOMAINS["sampler"] == 0x5C4ED


def test_gl014_registry_collision_is_flagged():
    """Two controllers registered onto ONE wire field inside the
    CONTROL_FIELDS dict is a GL014 hit — but only when linting the
    registry file's path (the pure-AST twin of the import-time
    uniqueness assert)."""
    src = """
        CONTROL_FIELDS = {
            "screen_adapt": "screen_mult",
            "speed_match": "speed_ratio",
            "span_cadence": "speed_ratio",
        }
    """
    vs = lint_source("commefficient_tpu/analysis/domains.py",
                     textwrap.dedent(src))
    assert [v.rule for v in vs] == ["GL014"]
    assert "collision" in vs[0].message
    # same dict under any other path is nobody's registry
    assert codes(src) == []


def test_gl014_shipped_registry_is_unique():
    from commefficient_tpu.analysis.domains import CONTROL_FIELDS
    assert len(set(CONTROL_FIELDS.values())) == len(CONTROL_FIELDS)
    # every shipped controller's (NAME, WIRE_FIELD) pair is registered
    from commefficient_tpu.control import (
        AdaptiveScreenController, SpanCadenceController,
        SpeedMatchController, StalenessDecayController,
    )
    for ctl in (AdaptiveScreenController, SpeedMatchController,
                SpanCadenceController, StalenessDecayController):
        assert CONTROL_FIELDS[ctl.NAME] == ctl.WIRE_FIELD


def _fixture_codes(src: str, path: str = "snippet.py"):
    return sorted({v.rule for v in lint_source(path,
                                               textwrap.dedent(src))})


@pytest.mark.parametrize("rule", sorted(ALL_RULES))
def test_rule_fires_on_positive_fixture(rule):
    pos, _, *path = FIXTURES[rule]
    assert rule in _fixture_codes(pos, *path), \
        f"{rule} failed to fire on its fixture"


@pytest.mark.parametrize("rule", sorted(ALL_RULES))
def test_rule_quiet_on_negative_fixture(rule):
    _, neg, *path = FIXTURES[rule]
    assert rule not in _fixture_codes(neg, *path), \
        f"{rule} false-positived"


def test_gl010_scoped_to_sharding_packages():
    """The same unregistered-axis source OUTSIDE parallel//federated/
    is not GL010's business (workload-specific meshes in tests or
    models name their own axes)."""
    assert "GL010" not in _fixture_codes(GL010_POS)
    assert "GL010" in _fixture_codes(
        GL010_POS, "commefficient_tpu/federated/snippet.py")


def test_gl015_flags_every_strided_subscript_in_the_traced_packages():
    """Both strided subscripts of the fixture, each with its reason;
    the same source outside ops/, federated/ and compress/ (a host
    loader striding a numpy array) is not GL015's business."""
    for pkg in ("ops", "federated", "compress"):
        vs = [v for v in lint_source(
            f"commefficient_tpu/{pkg}/snippet.py",
            textwrap.dedent(GL015_POS)) if v.rule == "GL015"]
        assert [v.line for v in vs] == [6, 9], vs
        assert "sq[::stride]" in vs[0].message
        assert all("gather" in v.message and "jax.lax.slice" in v.message
                   for v in vs)
    assert "GL015" not in _fixture_codes(
        GL015_POS, "commefficient_tpu/data/snippet.py")
    assert "GL015" not in _fixture_codes(GL015_POS)


def test_gl010_shard_map_mesh_argument_not_scanned():
    """shard_map's positional slot 1 is the MESH expression — string
    literals inside it (a registry lookup key, a label) are not axis
    names and must not false-positive; the axis_names KWARG is the
    sink."""
    src = """
        from jax import shard_map

        def wire(f, registry, specs):
            return shard_map(f, registry.lookup("emu2"), *specs)

        def bad(f, mesh, specs):
            return shard_map(f, mesh, *specs,
                             axis_names=frozenset({"cleints"}))
    """
    hits = _fixture_codes(src, "commefficient_tpu/parallel/snip.py")
    assert hits == ["GL010"]


def test_gl010_shipped_registry():
    from commefficient_tpu.analysis.domains import (
        CLIENTS_AXIS, MESH_AXES, MODEL_AXIS,
    )
    assert MESH_AXES == (CLIENTS_AXIS, MODEL_AXIS) == ("clients",
                                                       "model")


def test_gl011_scope_is_per_function():
    """A name bound from time.time() in ONE function must not taint
    the same name used as an ordinary parameter in another (the
    module-scope pass prunes nested function bodies)."""
    src = """
        import time

        def a():
            t0 = time.time()
            return t0

        def b(t0):
            # t0 here is an external wall-clock value (caller-supplied
            # timestamp): comparing against the wall clock is legal
            return time.time() - t0
    """
    assert "GL011" not in _fixture_codes(src)


def test_every_rule_documented():
    assert set(RULE_DOCS) == set(ALL_RULES)


# ---------------------------------------------------------------------------
# traced-scope mechanics: GL001/2/4 apply inside traced code only,
# including functions registered by call (scan/shard_map) and closures

def test_traced_scope_via_scan_registration():
    src = """
        import numpy as np
        import jax.lax as lax

        def body(carry, x):
            return carry + np.random.rand(), None

        def run(xs):
            return lax.scan(body, 0.0, xs)
    """
    assert "GL001" in codes(src)


def test_nested_closure_inherits_traced_scope():
    src = """
        import jax

        @jax.jit
        def outer(x):
            def inner(v):
                return v.item()
            return inner(x)
    """
    assert "GL002" in codes(src)


def test_gl003_nested_def_rebind_does_not_mask_outer_reuse():
    """A nested def rebinding `key` is a separate scope: it must not
    clear the outer function's drawn-key tracking (code-review
    regression — the nested assign used to discard the outer draw)."""
    src = """
        import jax

        def outer(key):
            a = jax.random.normal(key, (3,))

            def inner(k2):
                key = jax.random.fold_in(k2, 1)
                return jax.random.normal(key, (3,))

            b = jax.random.uniform(key, (3,))
            return a + b + inner(key)
    """
    assert "GL003" in codes(src)


def test_gl003_draw_inside_lambda_consumes_enclosing_key():
    src = """
        import jax

        def f(key, xs):
            a = jax.vmap(lambda i: jax.random.normal(key, (2,)))(xs)
            b = jax.random.uniform(key, (3,))
            return a, b
    """
    assert "GL003" in codes(src)


def test_host_code_not_traced_scope():
    src = """
        import numpy as np

        def host_only(x):
            return float(np.asarray(x).sum())
    """
    assert codes(src) == []


# ---------------------------------------------------------------------------
# suppressions + baseline

def test_per_line_suppression_silences_rule():
    src = """
        import time, jax

        @jax.jit
        def f(x):
            return x * time.time()  # graftlint: disable=GL001 -- test rig
    """
    assert codes(src) == []


def test_suppression_is_rule_specific():
    src = """
        import time, jax

        @jax.jit
        def f(x):
            return x * time.time()  # graftlint: disable=GL002
    """
    assert "GL001" in codes(src)


def test_syntax_error_is_lint_error():
    with pytest.raises(LintError):
        lint_source("bad.py", "def f(:\n")


def test_baseline_grandfathers_exact_counts():
    vs = [Violation("a.py", 3, 0, "GL006", "m"),
          Violation("a.py", 9, 0, "GL006", "m")]
    base = Baseline({("a.py", "GL006"): (2, "legacy cache writes")})
    new, stale = base.apply(vs)
    assert new == [] and stale == []


def test_baseline_reports_new_and_stale():
    base = Baseline({("a.py", "GL006"): (2, "legacy")})
    # tree improved: only one hit left -> stale entry must fail the run
    new, stale = base.apply([Violation("a.py", 3, 0, "GL006", "m")])
    assert new == [] and len(stale) == 1
    # regression: a third hit -> the group surfaces
    vs3 = [Violation("a.py", n, 0, "GL006", "m") for n in (3, 9, 12)]
    new, stale = base.apply(vs3)
    assert len(new) == 3  # whole group re-reported on overflow


def test_shipped_baseline_exactly_matches_tree():
    """The shipped baseline against a fresh scan of the shipped tree:
    no new violations, no stale entries. New hits fail CI; grandfathered
    ones (currently: none — the tree runs clean) don't."""
    baseline_path = os.path.join(REPO, "graftlint.baseline.json")
    with open(baseline_path) as f:
        raw = json.load(f)
    baseline = Baseline.load(baseline_path)
    violations = lint_paths([os.path.join(REPO, "commefficient_tpu")])
    # lint_paths reports repo-relative paths only when run from the
    # repo root; normalize to the baseline's path convention
    rel = [Violation(os.path.relpath(v.path, REPO).replace(os.sep, "/")
                     if os.path.isabs(v.path) else v.path,
                     v.line, v.col, v.rule, v.message)
           for v in violations]
    new, stale = baseline.apply(rel)
    assert new == [], "\n".join(v.render() for v in new)
    assert stale == [], "\n".join(stale)
    assert raw["version"] == 1


# ---------------------------------------------------------------------------
# runtime sanitizers: positive controls

def test_program_counter_counts_a_fresh_compile(sanitize):
    with sanitize.count_programs() as c:
        jax.jit(lambda x: x * 1.61803)(jnp.arange(5.0))
    assert c.count >= 1


def test_assert_program_count_rejects_extra_compiles(sanitize):
    with pytest.raises(AssertionError, match="program-count"):
        with sanitize.assert_program_count(0):
            jax.jit(lambda x: x * 2.71828)(jnp.arange(6.0))


def test_assert_program_count_allows_cache_hits(sanitize):
    f = jax.jit(lambda x: x * 3.14159)
    x = jnp.arange(7.0)
    x2 = x + 0.0  # eager op compiled OUTSIDE the counted block
    f(x)  # warm
    with sanitize.assert_program_count(0):
        f(x)
        f(x2)  # same shape/dtype: cpp cache hit, no compile


def test_forbid_transfers_blocks_implicit_host_to_device(sanitize):
    # the host->device direction: an np operand materialized at
    # dispatch is an implicit transfer. (On the CPU backend the
    # device->host read direction is zero-copy and escapes the guard —
    # on TPU it would trip too.)
    f = jax.jit(lambda v: v + 1.0)
    f(jnp.ones(3))  # warm with a device operand
    with sanitize.forbid_transfers():
        with pytest.raises(Exception, match="[Dd]isallow"):
            f(np.ones(3, np.float32))
    f(np.ones(3, np.float32))  # legal again outside


def test_forbid_transfers_allows_explicit_device_get(sanitize):
    x = jnp.arange(4.0)
    with sanitize.forbid_transfers():
        host = jax.device_get(x)
    np.testing.assert_array_equal(host, np.arange(4.0))

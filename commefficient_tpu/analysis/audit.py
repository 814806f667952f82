"""graftaudit: jaxpr-level auditor of the round engine's programs.

graftlint (engine/rules) sees SOURCE — it catches what syntax can
prove and nothing more. This module is the second analysis tier: it
traces the round programs the engine actually dispatches (the three
RoundBatch treedefs of federated/round.PROGRAM_VARIANTS, for a
sketch config plus a client-state-bearing one) to ClosedJaxprs
and walks the PROGRAM — post-closure, post-fusion, post-dispatch-
gating — for the contracts prose and AST can't check:

  AU001  forbidden host-interaction primitives: callbacks, debug
         prints, infeed/outfeed. Any of these inside a round program
         is a hidden per-round host sync (the cliff GL002 hunts
         syntactically; here it is caught even when smuggled in
         through a library call graftlint never sees).
  AU002  f64/c128 dtypes. The engine's numeric contract is
         f32 master state with bf16/int8 compute/wire options; a
         float64 appearing in a traced program is an accidental
         promotion (usually a Python float in the wrong place) that
         silently doubles state HBM — and TPUs execute it in slow
         emulation.
  AU003  exact `sort`/`top_k` over large static operands — the GL008
         regression class (~125 ms/round on TPU, PERF.md §1), caught
         here AFTER all dispatch gating, so a config routing around
         `approx_max_k` cannot hide.
  AU004  population scaling. Since ISSUE 9 the rule is STRICT for
         round programs: ANY population-shaped value — input, output,
         intermediate, or baked-in constant — is an error, because
         the cohort-gather/scatter-back state-motion programs are the
         only programs allowed to touch the [population, D] blocks
         and the jitted round operates purely on [num_workers, D]
         CohortState rows. The gather/scatter programs themselves
         (and configs listed in `population_inventory_configs`,
         for workloads that legitimately keep dense in-round state)
         keep the pre-ISSUE-9 INVENTORY semantics: population-shaped
         inputs/outputs are emitted as the named client-state map,
         intermediates/constants still error.
  AU005  dead-but-undonated round inputs: federated/round declares
         which dispatch operands the caller never reads again
         (ROUND_DEAD_ARGNUMS / SPAN_DEAD_ARGNUMS); each must be
         donated so XLA reuses its HBM in place. At population scale
         the client rows are the dominant allocation — an undonated
         dispatch transiently doubles them.
  AU006  static cost drift: every program's FLOPs/HBM-bytes price
         (analysis/costmodel) is diffed against the committed
         `audit.baseline.json` with graftlint-style exact-match
         semantics — a program missing from the baseline, a stale
         baseline entry, and a price drifted beyond the configured
         tolerance all error. The hardware-independent regression
         gate standing in for the TPU-pending bench numbers.

The auditor is config-driven from ``[tool.graftaudit]`` in
pyproject.toml and ships as the ``graftaudit`` console script
(scripts/audit.sh). Its cost
report is journaled as an ``audit_digest`` event
(telemetry/journal.py) and is bit-identical across runs — tracing is
deterministic, the report is canonical-JSON — which is what lets the
baseline diff be exact.

Import discipline: this module imports jax (and the round engine)
LAZILY, inside the functions that trace — `main` pins
JAX_PLATFORMS=cpu first so the auditor never claims an accelerator,
and importing the module (console-script resolution, graftlint's
pure-AST pass over this file) stays jax-free.
"""
from __future__ import annotations

import argparse
import dataclasses
import hashlib
import json
import os
import sys
from typing import Dict, List, Optional, Sequence, Tuple

from commefficient_tpu.analysis.costmodel import (
    aval_bytes, jaxpr_cost, sub_jaxprs,
)

AUDIT_RULE_DOCS = {
    "AU001": "forbidden host-interaction primitive (callback / debug "
             "print / infeed) inside a round program",
    "AU002": "f64/c128 dtype inside a round program (engine contract "
             "is f32 state, bf16/int8 compute/wire)",
    "AU003": "exact sort/top_k over a large static operand (the GL008 "
             "TPU sorting-network cliff, post-fusion)",
    "AU004": "population-scaling buffer that is not a declared "
             "client-state input/carried output",
    "AU005": "dead-after-dispatch round input not donated "
             "(round.ROUND_DEAD_ARGNUMS / SPAN_DEAD_ARGNUMS)",
    "AU006": "static cost drift vs audit.baseline.json (new / stale / "
             "regressed program)",
}

# AU001: primitive names that interact with the host mid-program
FORBIDDEN_PRIMITIVES = frozenset({
    "pure_callback", "io_callback", "debug_callback", "callback",
    "debug_print", "host_callback", "outside_call", "infeed",
    "outfeed",
})

# AU003 thresholds: exact top_k at or past GL008's static-k bound, and
# full sorts over operands big enough that the sorting network is the
# round's dominant cost
TOPK_MIN_K = 2048          # == rules.GL008_MIN_K (kept in sync by test)
SORT_MIN_N = 1 << 16

# the population sentinel the audit workload traces with: prime, and
# distinct from every other dimension in the geometry, so a shape
# "scales with num_clients" exactly when it contains this value
AUDIT_POPULATION = 23

# the synthetic workload geometry — small enough to trace in
# milliseconds, structured enough that every audited code path (sketch
# encode/decode, per-client state gather/scatter) is
# live. Committed baselines price THIS geometry; change it and the
# baseline must be regenerated.
AUDIT_GEOMETRY = dict(D=1024, W=8, B=4, k=64, rows=3, cols=256)

# the tiered config's device working set (ISSUE 11): >= W, divisible
# by every registered mesh clients-axis size (8 and 4) so the mesh
# tier shards the block without padding, and distinct from both the
# population sentinel and every geometry dim
TIER_WORKING_SET = 16


@dataclasses.dataclass(frozen=True, order=True)
class AuditFinding:
    program: str
    rule: str
    message: str

    def render(self) -> str:
        return f"{self.program}: {self.rule} {self.message}"


# ---------------------------------------------------------------------------
# jaxpr walkers (duck-typed like costmodel — sub_jaxprs is shared
# with it, so the auditor and the cost model can never disagree about
# which sub-jaxprs an equation contains)


def iter_eqns(jaxpr):
    """Every equation in `jaxpr` (Closed or raw), recursively."""
    inner = getattr(jaxpr, "jaxpr", None)
    if inner is not None and hasattr(inner, "eqns"):
        jaxpr = inner
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for sub in sub_jaxprs(v):
                yield from iter_eqns(sub)


def _shape_of(v):
    return tuple(getattr(getattr(v, "aval", None), "shape", ()) or ())


def _dtype_of(v):
    return getattr(getattr(v, "aval", None), "dtype", None)


def forbidden_primitive_findings(program: str, closed
                                 ) -> List[AuditFinding]:
    """AU001 + AU002 + AU003 over one traced program."""
    out: List[AuditFinding] = []
    for eqn in iter_eqns(closed):
        name = eqn.primitive.name
        if name in FORBIDDEN_PRIMITIVES:
            out.append(AuditFinding(
                program, "AU001",
                f"primitive `{name}` is a host interaction inside the "
                "round program — a per-round device->host sync; hoist "
                "it out of the traced round (telemetry exports at span "
                "boundaries exist for exactly this)"))
        for v in list(eqn.outvars) + [iv for iv in eqn.invars
                                      if hasattr(iv, "aval")]:
            dt = _dtype_of(v)
            if dt is not None and str(dt) in ("float64", "complex128"):
                out.append(AuditFinding(
                    program, "AU002",
                    f"{str(dt)} value of shape {_shape_of(v)} at "
                    f"primitive `{name}`: the engine's numeric "
                    "contract is f32 state / bf16-int8 compute; a "
                    "float64 is an accidental promotion (slow TPU "
                    "emulation, doubled HBM)"))
                break
        if name == "top_k":
            k = int(eqn.params.get("k", 0) or 0)
            operand = max((_shape_of(v)[-1] for v in eqn.invars
                           if _shape_of(v)), default=0)
            if k >= TOPK_MIN_K:
                out.append(AuditFinding(
                    program, "AU003",
                    f"exact `top_k` with k={k} over a [{operand}] "
                    "operand: lowers to a full sorting network on TPU "
                    "(~125 ms/round class, PERF.md §1); route through "
                    "approx_max_k or the fused threshold decode"))
        elif name == "sort":
            from commefficient_tpu.analysis.costmodel import sort_width
            width = sort_width(eqn)
            if width >= SORT_MIN_N:
                out.append(AuditFinding(
                    program, "AU003",
                    f"exact `sort` along a {width}-wide dimension "
                    "inside the round program: the TPU sorting-network "
                    "cliff; use an approximate selection or a fused "
                    "kernel (a sort along a SHORT dimension — the "
                    "sketch median's r-wide lane sort — is fine and "
                    "not flagged)"))
    # NO set-dedup: two distinct equations can produce identical
    # findings (same primitive, same shape), and each must count —
    # collapsing them would let a second occurrence hide behind a
    # count=1 baseline entry
    return sorted(out)


def population_scan(program: str, closed, population: int,
                    in_names: Sequence[str], out_names: Sequence[str],
                    strict: bool = False
                    ) -> Tuple[dict, List[AuditFinding]]:
    """AU004 + the named client-state inventory.

    strict=False (the state-motion programs; opted-out configs):
    inputs/outputs whose shape carries the population sentinel are
    INVENTORY (the dense per-client-state rows the gather/scatter
    programs legitimately move); any OTHER population-shaped value —
    an intermediate, or a constant baked into the program — is a
    finding.

    strict=True (round programs since ISSUE 9): population-shaped
    inputs/outputs are ERRORS too — the jitted round's operand
    surface is CohortState rows, and a population block reappearing
    there is the exact regression the O(active) refactor exists to
    prevent. The inventory block is still emitted (it must be empty —
    the refactor's mechanical definition of done)."""
    jaxpr = closed.jaxpr
    findings: List[AuditFinding] = []

    def pop_shaped(v):
        return population in _shape_of(v)

    inventory = {"inputs": [], "outputs": []}
    for v, name in zip(jaxpr.invars, in_names):
        if pop_shaped(v):
            inventory["inputs"].append({
                "name": name, "shape": list(_shape_of(v)),
                "dtype": str(_dtype_of(v)),
                "bytes": aval_bytes(v.aval)})
            if strict:
                findings.append(AuditFinding(
                    program, "AU004",
                    f"population-shaped INPUT `{name}` "
                    f"{list(_shape_of(v))}: round programs take only "
                    "cohort-sized operands — population state moves "
                    "through the gather/scatter state-motion programs "
                    "(ISSUE 9 O(active) contract)"))
    for v, name in zip(jaxpr.outvars, out_names):
        if pop_shaped(v):
            inventory["outputs"].append({
                "name": name, "shape": list(_shape_of(v)),
                "dtype": str(_dtype_of(v)),
                "bytes": aval_bytes(getattr(v, "aval", None))})
            if strict:
                findings.append(AuditFinding(
                    program, "AU004",
                    f"population-shaped OUTPUT `{name}` "
                    f"{list(_shape_of(v))}: round programs return only "
                    "cohort-sized results — scatter-back owns the "
                    "population write (ISSUE 9 O(active) contract)"))

    for cv, const in zip(jaxpr.constvars, closed.consts):
        if pop_shaped(cv):
            findings.append(AuditFinding(
                program, "AU004",
                f"population-shaped CONSTANT {list(_shape_of(cv))} "
                "baked into the program: a host-materialized "
                "num_clients-sized buffer rides into every dispatch"))

    # allowed var ids: program inputs and outputs, propagated through
    # container eqns positionally (a scatter under a pjit wrapper whose
    # result IS the program output is carried state, not a leak)
    allowed = {id(v) for v in jaxpr.invars}
    allowed |= {id(v) for v in jaxpr.outvars}

    def scan(jx, allowed):
        for eqn in jx.eqns:
            subs = [s for v in eqn.params.values()
                    for s in sub_jaxprs(v)]
            if subs:
                inner_allowed = set()
                for s in subs:
                    n_in = min(len(eqn.invars), len(s.invars))
                    for ev, sv in zip(eqn.invars[-n_in:],
                                      s.invars[-n_in:]):
                        if id(ev) in allowed:
                            inner_allowed.add(id(sv))
                    n_out = min(len(eqn.outvars), len(s.outvars))
                    for ev, sv in zip(eqn.outvars[-n_out:],
                                      s.outvars[-n_out:]):
                        if id(ev) in allowed:
                            inner_allowed.add(id(sv))
                for ov in eqn.outvars:
                    if pop_shaped(ov) and id(ov) not in allowed:
                        findings.append(AuditFinding(
                            program, "AU004",
                            f"population-shaped intermediate "
                            f"{list(_shape_of(ov))} produced by "
                            f"`{eqn.primitive.name}` is neither a "
                            "client-state input nor a carried output: "
                            "the round program materializes a "
                            "num_clients-scaling buffer per dispatch"))
                for s in subs:
                    scan(s, allowed | inner_allowed
                         | {id(v) for v in s.invars
                            if not pop_shaped(v)})
                continue
            for ov in eqn.outvars:
                if pop_shaped(ov) and id(ov) not in allowed:
                    findings.append(AuditFinding(
                        program, "AU004",
                        f"population-shaped intermediate "
                        f"{list(_shape_of(ov))} produced by "
                        f"`{eqn.primitive.name}` is neither a "
                        "client-state input nor a carried output: the "
                        "round program materializes a num_clients-"
                        "scaling buffer per dispatch"))

    scan(jaxpr, allowed)
    # no set-dedup — see forbidden_primitive_findings
    return inventory, sorted(findings)


def donation_findings(config_name: str, handle) -> List[AuditFinding]:
    """AU005: the dispatch entry points' dead operands vs what their
    jits actually donate (federated/round's registry attributes).
    Three entries since ISSUE 9: the cohort round program (its
    gathered CohortState is dead), the scatter-back state-motion
    program (the full ClientState is dead — at population scale THE
    donation that matters), and the scanned span."""
    from commefficient_tpu.federated.round import (
        ROUND_DEAD_ARGNUMS, SCATTER_DEAD_ARGNUMS, SPAN_DEAD_ARGNUMS,
    )
    out: List[AuditFinding] = []
    for entry, dead, donated, argname in (
            ("per-round", ROUND_DEAD_ARGNUMS,
             getattr(handle, "round_donate_argnums", ()),
             {0: "ServerState", 1: "CohortState"}),
            ("scatter-back", SCATTER_DEAD_ARGNUMS,
             getattr(handle, "scatter_donate_argnums", ()),
             {0: "ClientState"}),
            ("scanned-span", SPAN_DEAD_ARGNUMS,
             getattr(handle, "span_donate_argnums", ()),
             {0: "ServerState", 1: "ClientState"})):
        for argnum in dead:
            if argnum not in tuple(donated):
                out.append(AuditFinding(
                    f"{config_name}/{entry}", "AU005",
                    f"dispatch operand {argnum} "
                    f"({argname.get(argnum, '?')}) is dead after "
                    "dispatch (the caller only assigns state from the "
                    "result) but not donated: XLA cannot reuse its HBM "
                    "in place, transiently doubling the state "
                    "footprint — wire donate_argnums "
                    "(Config.donate_round_state)"))
    return out


# ---------------------------------------------------------------------------
# the audit workload: a synthetic linear model through the REAL round
# factory — make_train_fn is exactly what FedModel dispatches, so the
# traced jaxprs are the production programs at audit geometry


def audit_configs(population: int = AUDIT_POPULATION):
    """(name, Config) pairs the auditor traces. `sketch` pins the
    compression hot path; `client-state`
    (local_topk + local error + momentum + topk_down) is the config
    whose per-client rows populate the AU004 inventory. `population`
    overrides the num_clients sentinel (the mesh tier,
    analysis/shardaudit, needs one divisible by its clients axes)."""
    from commefficient_tpu.config import Config
    g = AUDIT_GEOMETRY
    base = dict(weight_decay=0.0, num_workers=g["W"],
                microbatch_size=-1, grad_size=g["D"],
                num_clients=population, seed=0)
    out = [("sketch", Config(
        mode="sketch", error_type="virtual", virtual_momentum=0.9,
        local_momentum=0.0, k=g["k"], num_rows=g["rows"],
        num_cols=g["cols"], num_blocks=1,
        **base).validate())]
    out.append(("client-state", Config(
        mode="local_topk", error_type="local", local_momentum=0.9,
        do_topk_down=True, k=g["k"], down_k=32,
        **base).validate()))
    # tiered cold client state (ISSUE 11): the same client-state
    # workload with a bounded device working set — its gather/scatter
    # trace over the [working_set, D] block (no population-shaped
    # value ANYWHERE, not even in the state-motion inventory: the
    # million-user residency claim as an audited program property).
    # TIER_WORKING_SET divides every registered mesh clients axis so
    # the mesh tier shards the block without padding.
    out.append(("client-state-tiered", Config(
        mode="local_topk", error_type="local", local_momentum=0.9,
        do_topk_down=True, k=g["k"], down_k=32, state_tier="host",
        state_working_set=TIER_WORKING_SET,
        **base).validate()))
    # value-fault screening (ISSUE 16): a sketch config with the norm
    # screen on traces the SCREENED program family — the only family
    # with the poison mask + screen-scalar operands — so the admission
    # arithmetic (finite mask, all_gather'd cohort median, survivor
    # fold) is priced and contract-checked like every other program.
    out.append(("sketch-screened", Config(
        mode="sketch", error_type="virtual", virtual_momentum=0.9,
        local_momentum=0.0, k=g["k"], num_rows=g["rows"],
        num_cols=g["cols"], num_blocks=1,
        update_screen="norm", **base).validate()))
    # Byzantine-robust aggregation (ISSUE 17): the screened sketch
    # config with a live adversary draw and the beta-trimmed mean —
    # traces the robust reduction (per-client gather, rank
    # computation, trim mask, residual gauge) riding the screened
    # program family, so the order-statistic arithmetic is priced and
    # contract-checked like every other program.
    out.append(("sketch-robust", Config(
        mode="sketch", error_type="virtual", virtual_momentum=0.9,
        local_momentum=0.0, k=g["k"], num_rows=g["rows"],
        num_cols=g["cols"], num_blocks=1,
        update_screen="norm", byzantine_rate=0.2, attack="sign_flip",
        aggregator="trimmed_mean", **base).validate()))
    # compressor plugins (ISSUE 19): the two new plugin families.
    # powersgd rides the client-state blocks (warm Q factors in the
    # velocities block, EF residual in errors) — its Gram-Schmidt /
    # factor-matmul arithmetic is priced and contract-checked like
    # every other program.
    out.append(("powersgd", Config(
        mode="powersgd", error_type="local", local_momentum=0.0,
        powersgd_rank=2, **base).validate()))
    # dp_sketch: the sketch pipeline plus per-client l2 clipping and
    # one post-aggregation Gaussian noise draw on the registered "dp"
    # PRNG domain — the privacy arithmetic traced in-program.
    out.append(("dp-sketch", Config(
        mode="dp_sketch", error_type="virtual", virtual_momentum=0.9,
        local_momentum=0.0, k=g["k"], num_rows=g["rows"],
        num_cols=g["cols"], num_blocks=1, dp_clip=1.0,
        dp_noise_mult=1.0, **base).validate()))
    return out


def build_workload(cfg):
    """Round handle + abstract operands for one audit config. All data
    is zeros — nothing here ever executes; make_jaxpr only reads
    shapes/dtypes/treedefs."""
    import jax
    import jax.numpy as jnp

    from commefficient_tpu.federated.round import (
        RoundBatch, audit_batch_variants, client_state_rows,
        init_client_state, init_server_state, make_train_fn,
    )
    from commefficient_tpu.ops.flat import flatten_params
    from commefficient_tpu.parallel.mesh import make_client_mesh

    g = AUDIT_GEOMETRY

    def loss_fn(params, batch, mask):
        x, y = batch
        pred = x @ params["w"]
        per_ex = 0.5 * (pred - y) ** 2
        denom = jnp.maximum(mask.sum(), 1.0)
        loss = (per_ex * mask).sum() / denom
        return loss, (loss,)

    params = {"w": jnp.zeros(g["D"], jnp.float32)}
    vec, unravel = flatten_params(params)
    # the audit mesh is ALWAYS one device: per-shard program shapes are
    # then host-count-independent, so the committed baseline prices the
    # same program on a laptop, in CI, and on a pod host
    mesh = make_client_mesh(1)
    handle = make_train_fn(loss_fn, unravel, cfg, mesh)
    server = init_server_state(cfg, vec)
    # client_state_rows: the tiered config (state_tier=host) allocates
    # its bounded [working_set, D] block — the gather/scatter the
    # auditor walks are then the slot-indexed tiered programs
    clients = init_client_state(
        cfg, client_state_rows(cfg, AUDIT_POPULATION), vec)
    batch = RoundBatch(
        jnp.arange(g["W"], dtype=jnp.int32),
        (jnp.zeros((g["W"], g["B"], g["D"]), jnp.float32),
         jnp.zeros((g["W"], g["B"]), jnp.float32)),
        jnp.ones((g["W"], g["B"]), jnp.float32))
    variants = audit_batch_variants(batch, cfg)
    lr = jnp.float32(0.1)
    key = jax.random.PRNGKey(0)
    return handle, server, clients, variants, lr, key


def _leaf_names(prefix: str, tree) -> List[str]:
    import jax
    leaves = jax.tree_util.tree_flatten_with_path(tree)[0]
    return [prefix + jax.tree_util.keystr(path)
            for path, _ in leaves]


def trace_variant(handle, server, clients, batch, lr, key):
    """(ClosedJaxpr, invar names, outvar names) of the single-round
    program this handle dispatches for `batch`'s treedef — the COHORT
    round body (round.make_train_fn round_step): the gathered
    CohortState avals come from jax.eval_shape over the gather body,
    so the traced operand surface is exactly what the round jit
    compiles and AU004-strict checks."""
    import jax
    cohort = jax.eval_shape(handle.gather_fn, clients,
                            batch.client_ids)
    closed, out_shape = jax.make_jaxpr(
        handle.round_step, return_shape=True)(
        server, cohort, batch, lr, key)
    in_names = (_leaf_names("server", server)
                + _leaf_names("cohort", cohort)
                + _leaf_names("batch", batch)
                + _leaf_names("lr", lr) + _leaf_names("key", key))
    out_names = _leaf_names("out", out_shape)
    return closed, in_names, out_names


def trace_state_motion(handle, clients, batch):
    """{"gather": (...), "scatter": (...)} — the two state-motion
    programs bracketing every round dispatch (round.
    STATE_MOTION_PROGRAMS), traced like trace_variant. These are the
    only programs ALLOWED to carry population-shaped inputs/outputs;
    their AU004 scan runs in inventory mode and their inventory IS
    the named client-state map the round programs no longer have."""
    import jax
    ids = batch.client_ids
    cohort = jax.eval_shape(handle.gather_fn, clients, ids)
    out = {}
    closed, g_shape = jax.make_jaxpr(
        handle.gather_fn, return_shape=True)(clients, ids)
    out["gather"] = (closed,
                     _leaf_names("clients", clients)
                     + _leaf_names("ids", ids),
                     _leaf_names("cohort", g_shape))
    closed, s_shape = jax.make_jaxpr(
        handle.scatter_fn, return_shape=True)(clients, ids, cohort)
    out["scatter"] = (closed,
                      _leaf_names("clients", clients)
                      + _leaf_names("ids", ids)
                      + _leaf_names("cohort", cohort),
                      _leaf_names("clients", s_shape))
    return out


# ---------------------------------------------------------------------------
# baseline: violations grandfathered graftlint-style + exact costs


class AuditBaseline:
    """audit.baseline.json: {"violations": [{program, rule, count,
    justification}], "costs": {program: {flops, hbm_bytes}}}. Same
    exact-match semantics as graftlint's Baseline: new hits AND stale
    entries both error, so the file can only change deliberately.

    COST_KEY / COST_FIELDS parameterize the per-program cost block so
    the mesh tier (analysis/shardaudit.MeshBaseline) reuses the whole
    diff machinery over its per-link byte report."""

    COST_KEY = "costs"
    COST_FIELDS = ("flops", "hbm_bytes")
    DRIFT_RULE = "AU006"

    def __init__(self, violations=None, costs=None):
        self.violations: Dict[Tuple[str, str], Tuple[int, str]] = dict(
            violations or {})
        self.costs: Dict[str, dict] = dict(costs or {})

    @classmethod
    def load(cls, path: str) -> "AuditBaseline":
        with open(path, encoding="utf-8") as f:
            raw = json.load(f)
        violations = {}
        for e in raw.get("violations", ()):
            violations[(e["program"], e["rule"])] = (
                int(e["count"]), e.get("justification", ""))
        return cls(violations, raw.get(cls.COST_KEY, {}))

    def dump(self, path: str) -> None:
        doc = {
            "version": 1,
            "violations": [
                {"program": p, "rule": r, "count": n,
                 "justification": j}
                for (p, r), (n, j) in sorted(self.violations.items())
            ],
            self.COST_KEY: {k: self.costs[k] for k in sorted(self.costs)},
        }
        tmp = path + ".tmp"
        with open(tmp, "w", encoding="utf-8") as f:
            f.write(json.dumps(doc, indent=2) + "\n")
        os.replace(tmp, path)

    def apply_violations(self, findings: Sequence[AuditFinding]
                         ) -> Tuple[List[AuditFinding], List[str]]:
        by_key: Dict[Tuple[str, str], List[AuditFinding]] = {}
        for f in findings:
            by_key.setdefault((f.program, f.rule), []).append(f)
        new: List[AuditFinding] = []
        stale: List[str] = []
        for key, fs in sorted(by_key.items()):
            if len(fs) > self.violations.get(key, (0, ""))[0]:
                new.extend(fs)
        for key, (count, _) in sorted(self.violations.items()):
            have = len(by_key.get(key, ()))
            if have < count:
                stale.append(
                    f"stale baseline entry {key[0]} {key[1]}: "
                    f"grandfathers {count}, audit found {have} — "
                    "regenerate with --write-baseline")
        return new, stale

    def apply_costs(self, costs: Dict[str, dict],
                    tolerance: float) -> List[AuditFinding]:
        out: List[AuditFinding] = []
        for prog in sorted(costs):
            got = costs[prog]
            base = self.costs.get(prog)
            if base is None:
                summary = ", ".join(f"{f}={got[f]}"
                                    for f in self.COST_FIELDS)
                out.append(AuditFinding(
                    prog, self.DRIFT_RULE,
                    f"no cost baseline for this program ({summary}); "
                    "a new program must be priced deliberately — run "
                    "--write-baseline and commit the diff"))
                continue
            for field in self.COST_FIELDS:
                want, have = int(base.get(field, 0)), int(got[field])
                lo = want * (1.0 - tolerance)
                hi = want * (1.0 + tolerance)
                if not (lo <= have <= hi):
                    direction = "regressed" if have > want else "moved"
                    out.append(AuditFinding(
                        prog, self.DRIFT_RULE,
                        f"static {field} {direction}: baseline {want}, "
                        f"traced {have} "
                        f"({(have - want) / max(want, 1):+.1%}, "
                        f"tolerance ±{tolerance:.1%}); if intentional, "
                        "--write-baseline and commit the diff"))
        for prog in sorted(self.costs):
            if prog not in costs:
                out.append(AuditFinding(
                    prog, self.DRIFT_RULE,
                    "stale cost baseline: program no longer traced by "
                    "the audit — regenerate with --write-baseline"))
        return out


# ---------------------------------------------------------------------------
# the shared graftaudit/graftmesh exit-code contract (ISSUE 8
# satellite): 0 clean, 1 rule violations, 2 baseline drift only —
# lives HERE (tier 2) because both CLIs depend on it and the mesh
# tier already imports this module, never the reverse. Drift is every
# *AU006-suffixed finding (AU006 cost drift, graftmesh's MAU006 link
# drift) plus stale baseline entries.


def split_findings(findings: Sequence[AuditFinding]
                   ) -> Tuple[List[AuditFinding], List[AuditFinding]]:
    """(rule violations, baseline drift)."""
    violations = [f for f in findings if not f.rule.endswith("AU006")]
    drift = [f for f in findings if f.rule.endswith("AU006")]
    return violations, drift


def exit_code(violations: Sequence, drift: Sequence,
              stale: Sequence) -> int:
    """0 clean, 1 rule violations (whatever else rode along), 2
    baseline drift only."""
    if violations:
        return 1
    if drift or stale:
        return 2
    return 0


# ---------------------------------------------------------------------------
# the full audit


def run_audit(inventory_configs: Sequence[str] = ()
              ) -> Tuple[dict, List[AuditFinding]]:
    """Trace every audit config x (round program variant + the two
    state-motion programs); return (report, findings). Findings carry
    AU001-AU005; AU006 (cost drift) is the caller's baseline diff —
    the report's `costs` block feeds it.

    Round programs are AU004-STRICT (population-shaped inputs/outputs
    error) unless the config name is in `inventory_configs` — the
    opt-out for workloads that legitimately keep dense in-round state
    ([tool.graftaudit] population_inventory_configs). The gather/
    scatter state-motion programs always run in inventory mode: their
    inventory is the named client-state map."""
    from commefficient_tpu.federated.round import program_variants_for

    programs: Dict[str, dict] = {}
    findings: List[AuditFinding] = []
    for cfg_name, cfg in audit_configs():
        strict = cfg_name not in set(inventory_configs)
        handle, server, clients, variants, lr, key = build_workload(cfg)
        findings.extend(donation_findings(cfg_name, handle))
        # per-config program set: default configs trace the three
        # default variants; screened configs (ISSUE 16) trace the two
        # screened ones instead
        for variant in program_variants_for(cfg):
            prog = f"{cfg_name}/{variant}"
            closed, in_names, out_names = trace_variant(
                handle, server, clients, variants[variant], lr, key)
            findings.extend(
                forbidden_primitive_findings(prog, closed))
            inventory, pop_findings = population_scan(
                prog, closed, AUDIT_POPULATION, in_names, out_names,
                strict=strict)
            findings.extend(pop_findings)
            programs[prog] = {
                "cost": jaxpr_cost(closed).as_dict(),
                "population_inventory": inventory,
            }
        # state motion is variant-independent (gather/scatter only see
        # client_ids) — trace it from whichever variant the config's
        # family provides
        motion_batch = variants.get("mask_free",
                                    variants.get("screened"))
        for motion, (closed, in_names, out_names) in \
                trace_state_motion(handle, clients,
                                   motion_batch).items():
            prog = f"{cfg_name}/{motion}"
            findings.extend(
                forbidden_primitive_findings(prog, closed))
            inventory, pop_findings = population_scan(
                prog, closed, AUDIT_POPULATION, in_names, out_names,
                strict=False)
            findings.extend(pop_findings)
            programs[prog] = {
                "cost": jaxpr_cost(closed).as_dict(),
                "population_inventory": inventory,
            }
    report = {
        "version": 1,
        "geometry": dict(AUDIT_GEOMETRY,
                         population=AUDIT_POPULATION),
        "programs": programs,
        "costs": {p: {"flops": d["cost"]["flops"],
                      "hbm_bytes": d["cost"]["hbm_bytes"]}
                  for p, d in programs.items()},
    }
    report["digest"] = report_digest(report)
    # no set-dedup — see forbidden_primitive_findings
    return report, sorted(findings)


def report_digest(report: dict) -> str:
    """sha256 over the canonical cost block — the bit-identical-across-
    runs claim is checked on exactly this value."""
    canon = json.dumps({"geometry": report["geometry"],
                        "costs": report["costs"]},
                       sort_keys=True, separators=(",", ":"))
    return hashlib.sha256(canon.encode()).hexdigest()


def journal_digest(journal_path: str, report: dict,
                   findings_count: int) -> dict:
    """Append the audit's cost report to a run journal as an
    `audit_digest` event (schema checked by telemetry.journal.
    validate_journal / scripts/journal_summary.py)."""
    from commefficient_tpu.telemetry.journal import append_event
    return append_event(
        journal_path, "audit_digest",
        digest=report["digest"],
        geometry=report["geometry"],
        programs=report["costs"],
        findings=int(findings_count))


# ---------------------------------------------------------------------------
# CLI


def main(argv: Optional[list] = None) -> int:
    # never claim an accelerator: the audit only traces
    os.environ.setdefault("JAX_PLATFORMS", "cpu")

    argv = list(sys.argv[1:] if argv is None else argv)
    # third-tier delegation: `graftaudit --mesh [...]` IS the mesh
    # audit (analysis/shardaudit, also shipped as `graftmesh`) — the
    # remaining args are interpreted by graftmesh's own parser, so
    # `graftaudit --mesh --write-baseline` regenerates
    # meshaudit.baseline.json, not audit.baseline.json
    if "--mesh" in argv or "--list-meshes" in argv:
        from commefficient_tpu.analysis import shardaudit
        return shardaudit.main([a for a in argv if a != "--mesh"])

    from commefficient_tpu.analysis.engine import load_pyproject_tool
    conf = load_pyproject_tool("graftaudit")
    ap = argparse.ArgumentParser(
        prog="graftaudit",
        description="jaxpr-level program auditor: forbidden "
                    "primitives, population scaling, buffer donation, "
                    "static cost baselines (rules AU001-AU006; see "
                    "--list-rules). --mesh runs the mesh-aware third "
                    "tier (graftmesh, rules AU007-AU011) instead; "
                    "--list-meshes shows its mesh registry.")
    ap.add_argument("--baseline",
                    default=conf.get("baseline", "audit.baseline.json"),
                    help="baseline file (grandfathered violations + "
                         "committed per-program costs)")
    ap.add_argument("--no-baseline", action="store_true",
                    help="report every finding and skip the cost diff")
    ap.add_argument("--write-baseline", action="store_true",
                    help="regenerate the baseline from this audit")
    ap.add_argument("--cost-tolerance", type=float,
                    default=float(conf.get("cost_tolerance", 0.0)),
                    help="relative cost drift allowed before AU006 "
                         "(default 0.0: exact match)")
    ap.add_argument("--inventory-configs", nargs="*",
                    default=list(conf.get(
                        "population_inventory_configs", [])),
                    help="audit-config names whose ROUND programs keep "
                         "the pre-ISSUE-9 AU004 inventory semantics "
                         "(population-shaped inputs/outputs reported, "
                         "not errored) — the opt-out for workloads "
                         "that legitimately keep dense in-round "
                         "client state")
    ap.add_argument("--journal", default="",
                    help="append the cost report to this JSONL run "
                         "journal as an `audit_digest` event")
    ap.add_argument("--report", action="store_true",
                    help="print the full JSON report to stdout")
    ap.add_argument("--list-rules", action="store_true")
    args = ap.parse_args(argv)

    if args.list_rules:
        from commefficient_tpu.analysis.shardaudit import MESH_RULE_DOCS
        for code, doc in sorted({**AUDIT_RULE_DOCS,
                                 **MESH_RULE_DOCS}.items()):
            print(f"{code}  {doc}")
        return 0

    report, findings = run_audit(
        inventory_configs=args.inventory_configs)

    if args.write_baseline:
        counts: Dict[Tuple[str, str], int] = {}
        for f in findings:
            counts[(f.program, f.rule)] = counts.get(
                (f.program, f.rule), 0) + 1
        AuditBaseline(
            {k: (n, "TODO: justify or fix") for k, n in counts.items()},
            report["costs"]).dump(args.baseline)
        print(f"graftaudit: wrote {len(findings)} grandfathered "
              f"finding(s) + {len(report['costs'])} program cost(s) "
              f"to {args.baseline}")
        return 0

    stale: List[str] = []
    if not args.no_baseline:
        baseline = (AuditBaseline.load(args.baseline)
                    if os.path.exists(args.baseline) else
                    AuditBaseline())
        new, stale = baseline.apply_violations(findings)
        cost_findings = baseline.apply_costs(
            report["costs"], args.cost_tolerance)
        findings = sorted(new + cost_findings)

    if args.report:
        print(json.dumps(report, indent=2, sort_keys=True))
    if args.journal:
        journal_digest(args.journal, report, len(findings))

    for f in findings:
        print(f.render())
    for msg in stale:
        print(f"graftaudit: {msg}")
    # exit-code contract shared with graftmesh (ISSUE 8 satellite):
    # 1 = rule violations (AU001-AU005), 2 = baseline drift only
    # (AU006 cost mismatch / stale entries) — CI can tell "the program
    # broke a contract" from "re-commit the baseline"
    violations, drift = split_findings(findings)
    rc = exit_code(violations, drift, stale)
    if rc:
        print(f"graftaudit: {len(violations)} violation(s), "
              f"{len(drift)} drift finding(s), {len(stale)} stale "
              f"baseline entr(ies)")
        return rc
    print(f"graftaudit: clean ({len(report['programs'])} program(s) "
          f"audited, digest {report['digest'][:12]})")
    return 0


if __name__ == "__main__":
    sys.exit(main())

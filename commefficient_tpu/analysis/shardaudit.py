"""graftmesh: sharding- and collective-aware program auditing.

graftlint (tier 1) proves what SYNTAX can prove; graftaudit (tier 2)
walks the traced single-device PROGRAM. Neither sees the property the
whole system is named for: FetchSGD's round is supposed to cost ONE
compressed all-reduce on the wire, and the ROADMAP's top two open
items (million-client sharded client state, multi-controller pod
scale-out) are sharding refactors that tier 1/2 would wave through
even when they break that contract. This module is the THIRD tier: it
traces the three round programs and the scanned span under EXPLICIT
multi-device meshes — the real constructors of parallel/mesh.py on a
simulated 8-device host platform — and walks the sharding-annotated
programs for the contracts only a mesh can express:

  AU007  large array (> --replicated-min-bytes) placed fully
         REPLICATED across the `clients` axis when a sharded spec
         exists (a dimension divides the axis). At population scale
         the dense client rows are the memory hazard; a replicated
         placement multiplies them by the device count.
  AU008  collective whose payload scales with the client POPULATION
         rather than the cohort: a psum/all_gather moving a
         [num_clients, ...] buffer turns the one-table wire contract
         into population-sized traffic. Detected via the same
         population-sentinel trick as audit.AU004.
  AU009  program input missing an explicit sharding — a dispatch
         operand carrying a single-device (default) placement on a
         multi-device mesh forces GSPMD to reshard it every round.
         The jaxpr-level twin of lint GL007.
  AU010  collective on the wrong LINK CLASS: a `model`-axis collective
         crossing DCN (the make_multihost_client_mesh layout puts
         model innermost exactly so this never happens), or more than
         one table-sized reduction crossing DCN per round (the
         mesh module's one-DCN-all-reduce-per-round invariant,
         previously only a docstring).
  AU011  resharding introduced BETWEEN round stages: a
         sharding_constraint / device_put equation that re-lays-out a
         value another constraint already pinned differently, or
         reshard-class equations present under the mesh that the
         single-device trace of the same program does not contain —
         each is a device-to-device transfer of round state the
         single-device program never pays.

Alongside the rules, every program × mesh gets a deterministic
PER-LINK COLLECTIVE REPORT (analysis/costmodel.collective_cost):
modeled bytes over intra-slice ICI vs inter-slice DCN and the
collective count by kind. The report is diffed exact-match against
the committed ``meshaudit.baseline.json`` and journaled as a
``mesh_audit_digest`` event — the acceptance gate the million-client
refactor lands against (cohort-sized collectives only) and the
before/after table the async/heavy-traffic work will cite.

Meshes audited (all built by the REAL parallel/mesh.py constructors,
so the audit exercises production layout code):

  clients8          1-D `clients` over 8 devices (pure ICI)
  clients4_model2   2-D clients x model, model innermost (pure ICI)
  multislice2       the slice-major multihost layout with an emulated
                    2-slice map (device i -> slice i % 2): the
                    `clients` axis spans DCN, `model` never does

Exit codes (shared with graftaudit, ISSUE 8 satellite): 0 clean,
1 rule violations (AU007-AU011 beyond the baseline), 2 baseline drift
only (link-report mismatch / stale entries) — so CI can distinguish
"the program broke a sharding contract" from "the program changed and
someone must re-commit the baseline".

Import discipline matches analysis/audit: jax imports live inside the
tracing functions; `main` pins JAX_PLATFORMS=cpu and forces the
8-device host platform BEFORE the first jax import.
"""
from __future__ import annotations

import argparse
import hashlib
import json
import os
import sys
from typing import Dict, List, Optional, Sequence, Tuple

from commefficient_tpu.analysis.audit import (
    AUDIT_GEOMETRY, AuditBaseline, AuditFinding, audit_configs,
    exit_code, iter_eqns, split_findings, _leaf_names,
)
from commefficient_tpu.analysis.costmodel import (
    CollectiveCost, MeshLinkModel, collective_cost,
)
from commefficient_tpu.analysis.domains import CLIENTS_AXIS, MODEL_AXIS

MESH_RULE_DOCS = {
    "AU007": "large array fully replicated across the `clients` axis "
             "when a sharded spec exists (> --replicated-min-bytes)",
    "AU008": "collective payload scales with the client POPULATION "
             "rather than the cohort",
    "AU009": "program input without an explicit NamedSharding on the "
             "audit mesh (jaxpr-level twin of lint GL007)",
    "AU010": "collective on the wrong link class: model-axis traffic "
             "over DCN, or > 1 table-sized DCN reduction per round",
    "AU011": "resharding between round stages the single-device "
             "program doesn't have (conflicting sharding constraints "
             "/ extra reshard equations under the mesh)",
}

# the population sentinel the mesh workload traces with. Divisible by
# every registered clients-axis size (8 and 4) so init_client_state
# pads nothing and the sentinel survives into the traced shapes
# verbatim; 184 = 8 * 23 collides with no other geometry dimension.
MESH_POPULATION = 184

# scanned-span trip count for the `span` program (small, fixed — the
# per-link report scales linearly with it and the baseline prices it)
SPAN_LEN = 2

# the three single-round treedefs, the two state-motion programs
# (cohort gather / scatter-back — since ISSUE 9 the only programs
# whose operands may carry the population dimension), and the scanned
# span — the full dispatch surface of federated/round.make_train_fn
MESH_PROGRAMS = ("mask_free", "dropout", "dropout_stragglers",
                 "gather", "scatter", "span")


def mesh_programs_for(cfg) -> tuple:
    """Per-config mesh program list: the config's steady-state round
    variants (federated/round.program_variants_for — the screened
    family for ISSUE 16 value-fault configs, the three defaults
    otherwise) plus the family-independent state-motion pair and the
    scanned span."""
    from commefficient_tpu.federated.round import program_variants_for
    return tuple(program_variants_for(cfg)) + ("gather", "scatter",
                                               "span")

# jaxpr equations that re-lay-out an existing value (AU011's
# reshard-class set)
_RESHARD_PRIMITIVES = frozenset({"sharding_constraint", "device_put"})


# ---------------------------------------------------------------------------
# mesh registry


def required_devices() -> int:
    return 8


def build_meshes(names: Optional[Sequence[str]] = None) -> Dict[str, dict]:
    """name -> {"mesh": Mesh, "link": MeshLinkModel, "slices": int}
    for every registered audit mesh (or the `names` subset). Requires
    the 8-device simulated host platform (main() forces it; tests get
    it from conftest)."""
    import jax

    from commefficient_tpu.parallel.mesh import (
        make_client_mesh, make_client_model_mesh,
        make_multihost_client_mesh,
    )

    if len(jax.devices()) < required_devices():
        raise RuntimeError(
            f"graftmesh needs {required_devices()} simulated devices "
            f"(have {len(jax.devices())}); run under XLA_FLAGS="
            "--xla_force_host_platform_device_count=8 (the graftmesh "
            "CLI sets this itself when jax is not yet imported)")

    registry = {
        "clients8": (lambda: make_client_mesh(8), 1),
        "clients4_model2": (lambda: make_client_model_mesh(4, 2), 1),
        "multislice2": (lambda: make_multihost_client_mesh(num_slices=2),
                        2),
    }
    picked = names or list(registry)
    out: Dict[str, dict] = {}
    for name in picked:
        try:
            builder, num_slices = registry[name]
        except KeyError:
            raise KeyError(
                f"unknown audit mesh {name!r}; registered: "
                f"{sorted(registry)}") from None
        mesh = builder()
        out[name] = {"mesh": mesh, "slices": num_slices,
                     "link": mesh_link_model(name, mesh, num_slices)}
    return out


def mesh_link_model(name: str, mesh, num_slices: int) -> MeshLinkModel:
    """Derive the per-axis link-class description from a real Mesh.

    An axis "spans DCN" when walking its devices (other axes pinned at
    coordinate 0) visits more than one slice. On real hardware the
    slice of a device is its `slice_index`; the emulated layout
    (make_multihost_client_mesh(num_slices=N) on single-slice/CPU
    devices) assigns device i -> slice i % N, matching the mesh
    module's own emulation."""
    import numpy as np

    arr = np.asarray(mesh.devices)
    real_slices = {int(getattr(d, "slice_index", 0) or 0)
                   for d in arr.flat}

    def slice_of(dev) -> int:
        if len(real_slices) > 1:
            # real multi-slice topology: the hardware map wins (same
            # precedence rule as make_multihost_client_mesh)
            return int(getattr(dev, "slice_index", 0) or 0)
        if num_slices > 1:
            # emulated slice map: device i -> slice i % N
            return int(dev.id) % num_slices
        return 0

    axes = list(mesh.axis_names)
    sizes = []
    slices = []
    for k, axis in enumerate(axes):
        lane = np.moveaxis(arr, k, 0).reshape(arr.shape[k], -1)[:, 0]
        spanned = len({slice_of(d) for d in lane})
        sizes.append((axis, int(arr.shape[k])))
        slices.append((axis, int(spanned)))
    return MeshLinkModel(name=name, axis_sizes=tuple(sizes),
                         axis_slices=tuple(slices))


# ---------------------------------------------------------------------------
# the mesh workload: the REAL round factory + the REAL multihost
# placement helpers, under each audit mesh


def build_mesh_workload(cfg, mesh):
    """Round handle + mesh-placed operands for one audit config. Every
    operand is constructed by the production placement path —
    init_server_state / init_client_state with the mesh, batch leaves
    through multihost.globalize/shard_rows (FedModel._feed's
    helpers) — so a placement regression in those constructors fires
    AU007/AU009 here rather than on a pod."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import PartitionSpec as P

    from commefficient_tpu.federated.round import (
        RoundBatch, client_state_rows, init_client_state,
        init_server_state, make_train_fn,
    )
    from commefficient_tpu.ops.flat import flatten_params
    from commefficient_tpu.parallel import multihost as mh

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
    handle = make_train_fn(loss_fn, unravel, cfg, mesh)
    server = init_server_state(cfg, vec, mesh=mesh)
    # the tiered config (ISSUE 11) shards its bounded [working_set, D]
    # block over the same clients axis — client_state_rows routes it
    clients = init_client_state(
        cfg, client_state_rows(cfg, MESH_POPULATION), vec, mesh=mesh)
    batch = RoundBatch(
        mh.globalize(mesh, P(), np.arange(g["W"], dtype=np.int32)),
        (mh.shard_rows(mesh, np.zeros((g["W"], g["B"], g["D"]),
                                      np.float32)),
         mh.shard_rows(mesh, np.zeros((g["W"], g["B"]), np.float32))),
        mh.shard_rows(mesh, np.ones((g["W"], g["B"]), np.float32)))
    # the three treedef variants, with the survivor/work operands
    # placed the way FedModel._call_train places them (explicit
    # globalize — round.audit_batch_variants builds host-default
    # operands, which AU009 would rightly flag on a multi-device mesh)
    ones = mh.globalize(mesh, P(), np.ones(g["W"], np.float32))
    half = mh.globalize(mesh, P(),
                        np.full(g["W"], 0.5, np.float32))
    from commefficient_tpu.federated.round import screened_family
    if screened_family(cfg):
        # screened family (ISSUE 16): the poison mask and the traced
        # screen-enable scalar are placed exactly the way the dispatch
        # path places them (globalize, replicated) — host-default
        # operands here would rightly fire AU009
        zeros = mh.globalize(mesh, P(), np.zeros(g["W"], np.float32))
        s_on = mh.globalize(mesh, P(), np.float32(1.0))
        variants = {
            "screened": batch._replace(
                survivors=ones, work=None, poison=zeros, screen=s_on),
            "screened_stragglers": batch._replace(
                survivors=ones, work=half, poison=zeros, screen=s_on),
        }
    else:
        variants = {
            "mask_free": batch._replace(survivors=None, work=None),
            "dropout": batch._replace(survivors=ones, work=None),
            "dropout_stragglers": batch._replace(survivors=ones,
                                                 work=half),
        }
    # the CONCRETE gathered cohort: executed through the production
    # jitted gather (explicit out_shardings), so the round variants'
    # cohort operands carry exactly the placement the dispatch path
    # produces — AU009/AU007 check the real thing
    cohort = handle.gather(clients, batch.client_ids)
    span = RoundBatch(
        mh.globalize(mesh, P(), np.tile(
            np.arange(g["W"], dtype=np.int32), (SPAN_LEN, 1))),
        (mh.shard_rows(mesh, np.zeros((SPAN_LEN, g["W"], g["B"],
                                       g["D"]), np.float32),
                       leading_axes=1),
         mh.shard_rows(mesh, np.zeros((SPAN_LEN, g["W"], g["B"]),
                                      np.float32), leading_axes=1)),
        mh.shard_rows(mesh, np.ones((SPAN_LEN, g["W"], g["B"]),
                                    np.float32), leading_axes=1))
    if screened_family(cfg):
        # the screened span scans the screened treedef: per-round
        # survivor/poison rows plus the per-round screen scalar lane
        span = span._replace(
            survivors=mh.globalize(mesh, P(), np.ones(
                (SPAN_LEN, g["W"]), np.float32)),
            poison=mh.globalize(mesh, P(), np.zeros(
                (SPAN_LEN, g["W"]), np.float32)),
            screen=mh.globalize(mesh, P(), np.ones(
                (SPAN_LEN,), np.float32)))
    lrs = mh.globalize(mesh, P(), np.full((SPAN_LEN,), 0.1, np.float32))
    lr = mh.globalize(mesh, P(), np.float32(0.1))
    key = mh.globalize(mesh, P(),
                       np.asarray(jax.random.PRNGKey(0)))
    return (handle, server, clients, cohort, variants, span, lr, lrs,
            key)


def trace_mesh_program(handle, server, clients, cohort, variants,
                       span, lr, lrs, key, program: str):
    """(ClosedJaxpr, input leaves with names) for one MESH_PROGRAMS
    entry. Input leaves are the CONCRETE mesh-placed operands (AU007 /
    AU009 read their .sharding); the jaxpr is what the per-round jit,
    the state-motion jits, or the scanned span compiles. The round
    variants take the gathered CohortState (ISSUE 9) — their operand
    surface is population-free; the gather/scatter programs are the
    ones carrying the sharded [population, D] blocks."""
    import jax

    if program == "span":
        args = (server, clients, span, lrs, key)
        closed = jax.make_jaxpr(handle.train_rounds)(*args)
        names = (_leaf_names("server", server)
                 + _leaf_names("clients", clients)
                 + _leaf_names("batch", span)
                 + _leaf_names("lr", lrs) + _leaf_names("key", key))
    elif program == "gather":
        # client_ids are identical across variants — take any
        ids = next(iter(variants.values())).client_ids
        args = (clients, ids)
        closed = jax.make_jaxpr(handle.gather_fn)(*args)
        names = (_leaf_names("clients", clients)
                 + _leaf_names("ids", ids))
    elif program == "scatter":
        ids = next(iter(variants.values())).client_ids
        args = (clients, ids, cohort)
        closed = jax.make_jaxpr(handle.scatter_fn)(*args)
        names = (_leaf_names("clients", clients)
                 + _leaf_names("ids", ids)
                 + _leaf_names("cohort", cohort))
    else:
        args = (server, cohort, variants[program], lr, key)
        closed = jax.make_jaxpr(handle.round_step)(*args)
        names = (_leaf_names("server", server)
                 + _leaf_names("cohort", cohort)
                 + _leaf_names("batch", variants[program])
                 + _leaf_names("lr", lr) + _leaf_names("key", key))
    leaves = jax.tree_util.tree_leaves(args)
    return closed, list(zip(names, leaves))


# ---------------------------------------------------------------------------
# rules


def _spec_axes(sharding) -> set:
    """Mesh axis names a NamedSharding's spec actually shards over."""
    spec = getattr(sharding, "spec", None) or ()
    axes = set()
    for entry in spec:
        if entry is None:
            continue
        if isinstance(entry, str):
            axes.add(entry)
        else:
            axes.update(e for e in entry if isinstance(e, str))
    return axes


def replication_findings(program: str, inputs, mesh,
                         min_bytes: int) -> List[AuditFinding]:
    """AU007 + AU009 over the concrete input operands."""
    from jax.sharding import NamedSharding

    out: List[AuditFinding] = []
    n_clients_axis = dict(
        zip(mesh.axis_names,
            mesh.devices.shape)).get(CLIENTS_AXIS, 1)
    for name, leaf in inputs:
        sharding = getattr(leaf, "sharding", None)
        shape = tuple(getattr(leaf, "shape", ()) or ())
        nbytes = int(getattr(leaf, "nbytes", 0) or 0)
        if not isinstance(sharding, NamedSharding):
            # covers BOTH a committed single-device placement and a
            # bare host array with no .sharding at all — the latter
            # is the most-unplaced case this rule exists to catch
            kind = (type(sharding).__name__ if sharding is not None
                    else "no placement (host array)")
            out.append(AuditFinding(
                program, "AU009",
                f"input `{name}` {list(shape)} carries "
                f"{kind} instead of an explicit "
                "NamedSharding on the audit mesh: GSPMD reshards it on "
                "every dispatch; place it with device_put / globalize "
                "/ shard_rows"))
            continue
        if (nbytes > min_bytes and n_clients_axis > 1
                and CLIENTS_AXIS not in _spec_axes(sharding)
                and any(d >= n_clients_axis and d % n_clients_axis == 0
                        for d in shape)):
            out.append(AuditFinding(
                program, "AU007",
                f"input `{name}` {list(shape)} ({nbytes} bytes) is "
                "fully replicated across the `clients` axis though a "
                "sharded spec exists (a dimension divides the "
                f"{n_clients_axis}-way axis): at population scale this "
                "multiplies the dominant allocation by the device "
                "count — shard it P('clients', ...)"))
    # no set-dedup (audit.forbidden_primitive_findings rationale)
    return sorted(out)


def collective_findings(program: str, cost: CollectiveCost,
                        population: int, table_bytes: int,
                        rounds_per_program: int) -> List[AuditFinding]:
    """AU008 + AU010 over one program's priced collectives."""
    out: List[AuditFinding] = []
    dcn_table_crossings = 0
    for rec in cost.records:
        if any(population in shape for shape in rec.operand_shapes):
            out.append(AuditFinding(
                program, "AU008",
                f"`{rec.kind}` over {list(rec.axes)} moves a "
                f"population-shaped payload {list(rec.operand_shapes)}"
                ": the wire cost scales with num_clients, not the "
                "cohort — gather the sampled rows before the "
                "collective"))
        if rec.crosses_dcn and MODEL_AXIS in rec.axes:
            out.append(AuditFinding(
                program, "AU010",
                f"`{rec.kind}` over the `model` axis crosses DCN: "
                "model-parallel collectives are per-layer traffic and "
                "must stay on intra-slice ICI (model axis innermost — "
                "parallel/mesh.make_multihost_client_mesh)"))
        if rec.crosses_dcn and rec.payload_bytes >= table_bytes:
            dcn_table_crossings += rec.mult
    if dcn_table_crossings > rounds_per_program:
        out.append(AuditFinding(
            program, "AU010",
            f"{dcn_table_crossings} table-sized (>= {table_bytes} B) "
            f"DCN reductions across {rounds_per_program} round(s): the "
            "round contract is ONE compressed all-reduce over DCN per "
            "round (make_multihost_client_mesh invariant) — fold the "
            "extra reduction into the table psum or keep it intra-"
            "slice"))
    return sorted(out)


def _reshard_eqns(closed) -> List[Tuple[str, str, object]]:
    """(primitive, sharding-repr, input var) of every reshard-class
    equation in a program, in walk order."""
    out = []
    for eqn in iter_eqns(closed):
        name = eqn.primitive.name
        if name not in _RESHARD_PRIMITIVES:
            continue
        if name == "sharding_constraint":
            spec = repr(eqn.params.get("sharding"))
        else:
            spec = repr(eqn.params.get("devices",
                                       eqn.params.get("device")))
        invar = eqn.invars[0] if eqn.invars else None
        outvar = eqn.outvars[0] if eqn.outvars else None
        out.append((name, spec, invar, outvar))
    return out


def reshard_findings(program: str, closed,
                     baseline_count: Optional[int]) -> List[AuditFinding]:
    """AU011: conflicting constraints within the program, plus
    reshard-class equations the single-device trace doesn't have."""
    out: List[AuditFinding] = []
    eqns = _reshard_eqns(closed)
    pinned: Dict[int, str] = {}
    for name, spec, invar, outvar in eqns:
        if invar is not None and id(invar) in pinned \
                and pinned[id(invar)] != spec:
            out.append(AuditFinding(
                program, "AU011",
                f"`{name}` re-lays-out a value another constraint "
                f"already pinned ({pinned[id(invar)]} -> {spec}): a "
                "device-to-device reshard between round stages — pick "
                "one layout for the value or reshard outside the "
                "round"))
        if outvar is not None:
            pinned[id(outvar)] = spec
    if baseline_count is not None and len(eqns) > baseline_count:
        out.append(AuditFinding(
            program, "AU011",
            f"{len(eqns)} reshard-class equation(s) under the mesh vs "
            f"{baseline_count} in the single-device trace of the same "
            "program: the mesh placement introduced device-to-device "
            "transfers the single-device program doesn't pay"))
    return sorted(out)


# ---------------------------------------------------------------------------
# baseline + report


class MeshBaseline(AuditBaseline):
    """meshaudit.baseline.json: grandfathered violations + the exact
    per-link report {program: {ici_bytes, dcn_bytes,
    dcn_collectives}}. Same exact-match semantics as the audit
    baseline; drift findings carry the MAU006 label so the CLIs can
    map them to exit code 2 (baseline drift) instead of 1 (rule
    violation)."""

    COST_KEY = "links"
    COST_FIELDS = ("ici_bytes", "dcn_bytes", "dcn_collectives")
    DRIFT_RULE = "MAU006"


def mesh_configs():
    """The audit-config surface, re-populated for the mesh tier: the
    sentinel must divide every registered clients axis so client-state
    rows carry it un-padded."""
    return audit_configs(population=MESH_POPULATION)


def run_mesh_audit(mesh_names: Optional[Sequence[str]] = None,
                   replicated_min_bytes: int = 1 << 20,
                   dcn_table_bytes: int = 1024,
                   ) -> Tuple[dict, List[AuditFinding]]:
    """Trace every config x mesh x program; return (report, findings).
    Findings carry AU007-AU011; the per-link drift (MAU006) is the
    caller's baseline diff over report["links"]."""
    from commefficient_tpu.parallel.mesh import make_client_mesh

    meshes = build_meshes(mesh_names)
    programs: Dict[str, dict] = {}
    findings: List[AuditFinding] = []
    for cfg_name, cfg in mesh_configs():
        # single-device reshard baseline, shared across meshes: the
        # same program traced on the 1-device mesh (AU011's "the
        # single-device program doesn't have" reference)
        cfg_programs = mesh_programs_for(cfg)
        single = build_mesh_workload(cfg, make_client_mesh(1))
        single_counts = {}
        for program in cfg_programs:
            closed_1, _ = trace_mesh_program(*single, program)
            single_counts[program] = len(_reshard_eqns(closed_1))
        for mesh_name, entry in meshes.items():
            mesh, link = entry["mesh"], entry["link"]
            workload = build_mesh_workload(cfg, mesh)
            for program in cfg_programs:
                prog = f"{cfg_name}/{program}@{mesh_name}"
                closed, inputs = trace_mesh_program(*workload, program)
                cost = collective_cost(closed, link)
                rounds = SPAN_LEN if program == "span" else 1
                findings.extend(replication_findings(
                    prog, inputs, mesh, replicated_min_bytes))
                findings.extend(collective_findings(
                    prog, cost, MESH_POPULATION, dcn_table_bytes,
                    rounds))
                findings.extend(reshard_findings(
                    prog, closed, single_counts[program]))
                programs[prog] = cost.as_dict()
    report = {
        "version": 1,
        "geometry": dict(AUDIT_GEOMETRY, population=MESH_POPULATION,
                         span_len=SPAN_LEN),
        "meshes": {name: entry["link"].as_dict()
                   for name, entry in sorted(meshes.items())},
        "programs": programs,
        "links": {p: {"ici_bytes": d["ici_bytes"],
                      "dcn_bytes": d["dcn_bytes"],
                      "dcn_collectives": d["dcn_collectives"]}
                  for p, d in programs.items()},
    }
    report["digest"] = report_digest(report)
    return report, sorted(findings)


def report_digest(report: dict) -> str:
    """sha256 over the canonical per-link block — the bit-identical-
    across-runs claim is checked on exactly this value."""
    canon = json.dumps({"geometry": report["geometry"],
                        "meshes": report["meshes"],
                        "links": report["links"]},
                       sort_keys=True, separators=(",", ":"))
    return hashlib.sha256(canon.encode()).hexdigest()


def journal_digest(journal_path: str, report: dict,
                   findings_count: int) -> dict:
    """Append the per-link report as a `mesh_audit_digest` event
    (schema checked by telemetry.journal.validate_journal)."""
    from commefficient_tpu.telemetry.journal import append_event
    return append_event(
        journal_path, "mesh_audit_digest",
        digest=report["digest"],
        geometry=report["geometry"],
        meshes=report["meshes"],
        programs=report["links"],
        findings=int(findings_count))


# ---------------------------------------------------------------------------
# CLI (also reachable as `graftaudit --mesh`)


def force_host_devices(n: int = 8) -> None:
    """Arrange for `n` simulated host devices BEFORE the first jax
    import. A no-op when the flag is already present (conftest) or jax
    is already imported (build_meshes then validates the count)."""
    if "jax" in sys.modules:
        return
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={n}"
        ).strip()


# the shared exit-code contract (split_findings / exit_code) lives in
# analysis/audit — tier 2, which this module already depends on — and
# is re-exported here for callers that think in mesh-tier terms


def main(argv: Optional[list] = None) -> int:
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    force_host_devices(required_devices())

    from commefficient_tpu.analysis.engine import load_pyproject_tool
    conf = load_pyproject_tool("graftmesh")
    ap = argparse.ArgumentParser(
        prog="graftmesh",
        description="mesh-aware program auditor: replication, "
                    "population-scaling collectives, link-class "
                    "placement, resharding, and the per-link "
                    "ICI/DCN byte baseline (rules AU007-AU011; "
                    "see --list-rules)")
    ap.add_argument("--baseline",
                    default=conf.get("baseline",
                                     "meshaudit.baseline.json"))
    ap.add_argument("--no-baseline", action="store_true",
                    help="report every finding and skip the link diff")
    ap.add_argument("--write-baseline", action="store_true",
                    help="regenerate the baseline from this audit")
    ap.add_argument("--meshes", nargs="*",
                    default=list(conf.get("meshes", [])) or None,
                    help="subset of the mesh registry to audit")
    ap.add_argument("--replicated-min-bytes", type=int,
                    default=int(conf.get("replicated_min_bytes",
                                         1 << 20)),
                    help="AU007 fires on replicated arrays above this")
    ap.add_argument("--dcn-table-bytes", type=int,
                    default=int(conf.get("dcn_table_bytes", 1024)),
                    help="payload at/above which a DCN reduction "
                         "counts against the once-per-round budget")
    ap.add_argument("--journal", default="",
                    help="append the report to this JSONL run journal "
                         "as a `mesh_audit_digest` event")
    ap.add_argument("--report", action="store_true",
                    help="print the full JSON report to stdout")
    ap.add_argument("--list-rules", action="store_true")
    ap.add_argument("--list-meshes", action="store_true")
    args = ap.parse_args(argv)

    if args.list_rules:
        for code, doc in sorted(MESH_RULE_DOCS.items()):
            print(f"{code}  {doc}")
        return 0
    if args.list_meshes:
        for name, entry in sorted(build_meshes().items()):
            link = entry["link"]
            print(f"{name}  axes={dict(link.axis_sizes)} "
                  f"dcn_spans={dict(link.axis_slices)}")
        return 0

    report, findings = run_mesh_audit(
        args.meshes,
        replicated_min_bytes=args.replicated_min_bytes,
        dcn_table_bytes=args.dcn_table_bytes)

    if args.write_baseline:
        counts: Dict[Tuple[str, str], int] = {}
        for f in findings:
            counts[(f.program, f.rule)] = counts.get(
                (f.program, f.rule), 0) + 1
        MeshBaseline(
            {k: (n, "TODO: justify or fix") for k, n in counts.items()},
            report["links"]).dump(args.baseline)
        print(f"graftmesh: wrote {len(findings)} grandfathered "
              f"finding(s) + {len(report['links'])} program link "
              f"report(s) to {args.baseline}")
        return 0

    stale: List[str] = []
    if not args.no_baseline:
        baseline = (MeshBaseline.load(args.baseline)
                    if os.path.exists(args.baseline) else
                    MeshBaseline())
        new, stale = baseline.apply_violations(findings)
        drift_findings = baseline.apply_costs(report["links"],
                                              tolerance=0.0)
        findings = sorted(new + drift_findings)

    if args.report:
        print(json.dumps(report, indent=2, sort_keys=True))
    if args.journal:
        journal_digest(args.journal, report, len(findings))

    violations, drift = split_findings(findings)
    for f in findings:
        print(f.render())
    for msg in stale:
        print(f"graftmesh: {msg}")
    rc = exit_code(violations, drift, stale)
    if rc:
        print(f"graftmesh: {len(violations)} violation(s), "
              f"{len(drift)} drift finding(s), {len(stale)} stale "
              f"baseline entr(ies)")
        return rc
    print(f"graftmesh: clean ({len(report['programs'])} program(s) "
          f"across {len(report['meshes'])} mesh(es), digest "
          f"{report['digest'][:12]})")
    return 0


if __name__ == "__main__":
    sys.exit(main())

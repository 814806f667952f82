"""Central registries: PRNG-domain tags, mesh axis names, and the
host-concurrency contracts (shared-state guards + durability-ordering
edges) graftsync enforces.

The engine's determinism story rests on DOMAIN SEPARATION: the dropout,
straggler, and scheduler draws are each a pure function of
``(seed, domain, round_idx)`` on a counter-based generator, so the
three streams never alias each other and a resumed run replays all of
them bit-exactly (utils/faults, scheduler/policy). That only holds
while the domain tags stay DISTINCT — a collision silently correlates
two "independent" failure processes, the exact class of bug that is
invisible at runtime and catastrophic in a convergence study.

Before this registry the tags lived as inline hex literals in the
modules that drew from them; nothing enforced uniqueness, and a new
subsystem picking a tag had to grep for collisions by hand. Now:

  * every domain constant lives HERE, keyed by a name that documents
    its consumer;
  * uniqueness is asserted at import time (and, pure-AST, by graftlint
    rule GL009, which also flags inline hex literals inside
    ``fold_in``/``SeedSequence`` calls anywhere in the tree — new
    draws must route through this registry);
  * consumers import the tag by name, so the registry is the single
    place a reviewer audits the stream layout.

Deliberately dependency-free (stdlib only): `utils/faults` and
`scheduler/policy` import this at module load, and graftlint parses it
without executing anything.
"""
from __future__ import annotations

# name -> domain tag. Tags are arbitrary distinct integers; the hex
# spellings are mnemonic ("0D120" ~ Dropout, "51044" ~ SLOw, "5C4ED" ~
# SChED) and FROZEN — changing a value changes every historical run's
# fault/schedule replay, so tags may be added but never edited.
DOMAINS = {
    "dropout": 0x0D120,    # utils/faults.bernoulli_survivors
    "straggler": 0x51044,  # utils/faults.straggler_work_fractions
    "sampler": 0x5C4ED,    # scheduler/policy.ThroughputAwareSampler
    "poison": 0xBAD0D,     # utils/faults.poison_mask (value faults)
    "byzantine": 0xB42A1,  # utils/faults.byzantine_mask (adversaries)
    "dp": 0xD9A05,         # compress/dp_sketch per-round Gaussian noise
    "powersgd": 0x909D0,   # compress/powersgd fresh-client Q warm start
}

_values = list(DOMAINS.values())
assert len(set(_values)) == len(_values), (
    "PRNG domain collision in analysis/domains.DOMAINS: two streams "
    "sharing a tag are perfectly correlated")


def domain(name: str) -> int:
    """The registered domain tag for `name`; KeyError (with the known
    names listed) on a typo rather than a silent new stream."""
    try:
        return DOMAINS[name]
    except KeyError:
        raise KeyError(
            f"unknown PRNG domain {name!r}; registered: "
            f"{sorted(DOMAINS)} (add new streams to analysis/domains)"
        ) from None


# ---------------------------------------------------------------------------
# mesh-axis registry (ISSUE 8 satellite; enforced by graftlint GL010)
#
# The engine's sharding story names exactly two mesh axes: `clients`
# (the federated parallel axis every round program shards over) and
# `model` (optional tensor parallelism, innermost so its collectives
# ride the fastest ICI). Before this registry the names lived as
# string literals spread across parallel/ and federated/; a typo
# ("cleints") or an unregistered new axis produced a silently
# replicated spec — the layout bug class GSPMD propagation hides
# until a pod run reshards every dispatch. GL010 holds the line: an
# axis-name string literal in a sharding construction under parallel/
# or federated/ that is not a MESH_AXES value is a lint error, and the
# mesh constructors themselves build their axis_names from these
# constants. (ring_attention's `seq` axis is caller-named — it takes
# the axis as a parameter and registers no literal of its own.)

CLIENTS_AXIS = "clients"
MODEL_AXIS = "model"
MESH_AXES = (CLIENTS_AXIS, MODEL_AXIS)

assert len(set(MESH_AXES)) == len(MESH_AXES), (
    "duplicate axis name in analysis/domains.MESH_AXES")


# ---------------------------------------------------------------------------
# shared-state guard registry (ISSUE 14; enforced by graftsync SY001)
#
# The host control plane is thread-rich since PRs 10-13: the journal /
# checkpoint / spill bounded-queue writer threads, pipelined staging,
# and the per-thread trace rings all mutate state that another thread
# reads. The discipline — "this attribute is only touched under that
# lock" — lived in docstrings; this registry is the ONE place it is
# declared, and graftsync SY001 holds the line mechanically: a
# mutation of a registered `Class.attr` outside a `with self.<guard>:`
# block is an audit error, and an attribute the cross-thread scan
# proves shared (mutated both from a thread-entry function and from
# outside one) that is NOT registered is an error too — new shared
# state must be declared here with its guard, exactly like a new PRNG
# stream must be declared in DOMAINS.
#
# "Class.attr" -> guard lock attribute on the same instance.
SHARED_STATE = {
    # telemetry/trace.py — per-thread span rings, appended by every
    # producing thread (incl. the writer threads), drained by the
    # flush path
    "Tracer._rings": "_lock",
    "Tracer._dropped": "_lock",
    # federated/statestore.py — the spill writer commits to the tail
    # and retires pending entries while producers read/restore rows
    "TieredStateStore._tail": "_lock",
    "TieredStateStore._pending": "_lock",
    "TieredStateStore._warm": "_lock",
    # ISSUE 16 checksummed tiers: per-row CRCs are recorded by the
    # spill writer's commit and read/invalidated by the restore path's
    # verification; quarantine events are appended at verification
    # time and drained by the telemetry emitter
    "TieredStateStore._sums": "_lock",
    "TieredStateStore._quarantined": "_lock",
    # utils/checkpoint.py — the deferred writer failure is stored on
    # the writer thread and consumed (cleared) on the caller's thread
    "AsyncCheckpointWriter._exc": "_exc_lock",
}

assert all(g for g in SHARED_STATE.values()), (
    "every SHARED_STATE entry must name its guard lock attribute")


# ---------------------------------------------------------------------------
# durability-ordering registry (ISSUE 14; enforced by graftsync SY006)
#
# The control plane's crash-safety and resume-bit-exactness rest on a
# handful of happens-before edges between host calls — "the write-
# ahead journal flush runs before the dispatch that executes the
# plan", "the spill queue drains before the checkpoint payload reads
# the tail". Each edge below names one such contract as call-order
# DOMINANCE inside one registered function: every call of `after`
# must appear (in source order) after at least one call of `before`,
# and BOTH must be present — so a refactor that deletes or reorders a
# barrier turns the audit red instead of silently shipping a torn
# journal or a stale tail. Names are frozen (tests and README refer
# to them); edges may be added but never weakened in place.
ORDERING_EDGES = {
    # ISSUE 12 write-ahead contract: every sealed RoundPlan of a span
    # is durable before the span's dispatch executes it (the journal
    # is the authoritative decision log a takeover replays).
    "wal-flush-before-dispatch": {
        "path": "commefficient_tpu/federated/api.py",
        "function": "dispatch_rounds",
        "before": "_flush_write_ahead",
        "after": "with_retries",
        "why": "a plan executed before its journal line is durable "
               "cannot be replayed by a coordinator takeover",
    },
    # ISSUE 11 mid-spill contract: the checkpoint payload reads the
    # host tail only after every queued spill has committed to it.
    "spill-drain-before-checkpoint-payload": {
        "path": "commefficient_tpu/federated/statestore.py",
        "function": "checkpoint_rows",
        "before": "flush",
        "after": "get_many",
        "why": "a payload built from a tail with spills still in "
               "flight loses evicted client rows (error-feedback "
               "state) on resume",
    },
    # ISSUE 10 writer contract: the async checkpoint writer drains
    # before any SYNCHRONOUS save so the manifest rotates in order.
    "writer-drain-before-save-final": {
        "path": "commefficient_tpu/training/cv_train.py",
        "function": "main",
        "before": "drain_persistence",
        "after": "save_final",
        "why": "a final save overtaking queued rotating saves rotates "
               "the manifest out of order (resume picks a stale "
               "newest)",
    },
    "writer-drain-before-save-final-gpt2": {
        "path": "commefficient_tpu/training/gpt2_train.py",
        "function": "main",
        "before": "drain_persistence",
        "after": "save_final",
        "why": "same manifest-ordering contract as the CV driver",
    },
    # ISSUE 16 integrity contract: every host tail row is checksum-
    # verified (and, on mismatch, quarantined back to its init value)
    # BEFORE the restore scatter installs it in a device slot — the
    # verified read happens inside _rows_for, so the scatter dispatch
    # must dominate it in source order. A reorder here would feed a
    # bit-rotted memmap row straight into the next round's gather.
    "checksum-verify-before-restore": {
        "path": "commefficient_tpu/federated/statestore.py",
        "function": "_restore_chunk",
        "before": "_rows_for",
        "after": "scatter",
        "why": "a restore that scatters tail rows before their "
               "checksum verification installs silently corrupted "
               "error-feedback state on the device",
    },
    # ISSUE 11 WAR hazard: the spill gather's device barrier must run
    # before its rows are handed to the writer — the donating restore
    # scatter that follows overwrites the gathered slots in place, a
    # write jax does not order against the dependency-free gather.
    "gather-barrier-before-donated-scatter": {
        "path": "commefficient_tpu/federated/statestore.py",
        "function": "_spill_chunk",
        "before": "block_until_ready",
        "after": "submit",
        "why": "without the barrier the donated scatter's in-place "
               "write races the spill gather's read of the same "
               "buffer (observed as heap corruption / garbage rows)",
    },
}

for _name, _edge in ORDERING_EDGES.items():
    assert {"path", "function", "before", "after", "why"} <= set(_edge), (
        f"ORDERING_EDGES[{_name!r}] is missing a required field")
    assert _edge["before"] != _edge["after"], (
        f"ORDERING_EDGES[{_name!r}]: before and after name the same "
        "call — the edge is vacuous")


# ---------------------------------------------------------------------------
# precision-seam registry (ISSUE 18; enforced by graftnum NU002)
#
# The engine's numeric contract is f32 master state end to end; every
# place a value deliberately LOSES precision — the PR-6 sketch-table
# wire quantization, the flash-attention output cast back to the
# activation dtype — is a SEAM the convergence analysis must account
# for (the quantization rounding rides the error-feedback residual,
# PERF.md round 6). Before this registry those seams lived as .astype
# calls spread through ops/; nothing stopped a refactor from adding a
# new silent downcast on a path the analysis assumes exact. graftnum
# NU002 holds the line at the PROGRAM level: every lossy
# `convert_element_type` in a traced round program must match a
# (src, dst) pair registered here, and an unregistered downcast is an
# audit error — new seams must be declared (and their residual story
# told in `why`) before they ship. Upcasts and exact index casts
# (float -> int32/int64) are not seams and need no entry.
#
# Dtype names are the str() of the jax/numpy dtype ("float32",
# "bfloat16", "int8"), kept as strings so this module stays
# stdlib-only.
PRECISION_SEAMS = {
    "sketch-wire-bf16": {
        "src": "float32", "dst": "bfloat16",
        "path": "commefficient_tpu/ops/quant.py",
        "function": "quantize_table",
        "why": "the bf16 sketch-table wire format (PR 6): the rounding "
               "is bounded per-cell and lands in the error-feedback "
               "residual, which FetchSGD re-transmits",
    },
    "sketch-wire-int8": {
        "src": "float32", "dst": "int8",
        "path": "commefficient_tpu/ops/quant.py",
        "function": "quantize_table",
        "why": "the int8 symmetric sketch-table wire format (PR 6): "
               "per-row scale rides beside the payload, quantization "
               "noise lands in the error-feedback residual",
    },
    "attention-output-cast": {
        "src": "float32", "dst": "bfloat16",
        "path": "commefficient_tpu/ops/attention.py",
        "function": "flash_attention",
        "why": "the flash-attention f32 accumulator is cast back to "
               "the bf16 activation dtype on exit — the standard "
               "mixed-precision activation seam, outside the "
               "error-feedback loop",
    },
}

for _name, _seam in PRECISION_SEAMS.items():
    assert {"src", "dst", "path", "function", "why"} <= set(_seam), (
        f"PRECISION_SEAMS[{_name!r}] is missing a required field")
    assert _seam["src"] != _seam["dst"], (
        f"PRECISION_SEAMS[{_name!r}]: src and dst name the same dtype "
        "— the seam is vacuous")


def precision_seam_pairs() -> set:
    """The registered (src dtype name, dst dtype name) pairs — what
    graftnum NU002 matches traced convert_element_type eqns against."""
    return {(s["src"], s["dst"]) for s in PRECISION_SEAMS.values()}


# ---------------------------------------------------------------------------
# controller wire-field registry (ISSUE 20; enforced by graftlint GL014)
#
# The control/ subsystem's replay contract rides each controller's
# adjusted value on a named RoundPlan wire field ("controls" payload
# key, see parallel/plantransport.serialize_plan): the journaled plan
# stream is the authoritative adjustment log a takeover replays, so a
# wire-field collision means two controllers silently overwrite each
# other's decisions on the wire — invisible at runtime, catastrophic
# on a resume. This registry is the ONE place wire fields are claimed,
# mirroring the DOMAINS discipline: controller name -> wire field,
# uniqueness asserted at import time and re-proven pure-AST by
# graftlint GL014 (which also flags any `WIRE_FIELD = "..."` class
# attribute in the tree whose literal is not registered here). Names
# and fields are FROZEN once shipped — a renamed field orphans every
# historical journal's plan stream.
CONTROL_FIELDS = {
    "screen_adapt": "screen_mult",      # control/screen (ISSUE 17)
    "speed_match": "speed_ratio",       # control/speed
    "span_cadence": "scan_span",        # control/span
    "staleness_decay": "staleness_decay",  # control/staleness
}

_fields = list(CONTROL_FIELDS.values())
assert len(set(_fields)) == len(_fields), (
    "controller wire-field collision in analysis/domains."
    "CONTROL_FIELDS: two controllers sharing a plan wire field "
    "silently overwrite each other's journaled adjustments")


def control_field(name: str) -> str:
    """The registered plan wire field for controller `name`; KeyError
    (with the known names listed) on a typo rather than a silent new
    wire field."""
    try:
        return CONTROL_FIELDS[name]
    except KeyError:
        raise KeyError(
            f"unknown controller {name!r}; registered: "
            f"{sorted(CONTROL_FIELDS)} (add new controllers to "
            "analysis/domains.CONTROL_FIELDS)"
        ) from None

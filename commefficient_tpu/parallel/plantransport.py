"""Coordinator-broadcast control plane (ISSUE 12).

Every non-default scheduling decision — throughput-aware participant
sampling, deadlines, buffered async admission — used to be
single-controller only, because the decisions read process-local state
(wall-clock throughput EMAs, the admit buffer). This module makes the
coordinator's per-round `RoundPlan` an AUTHORITATIVE broadcast control
stream instead:

  * the coordinator serializes each round's plan (participants, work
    fractions, deadlines — scheduler.RoundPlan) to a fixed small
    host-side payload and broadcasts it ONCE per round;
  * every process — the coordinator included — installs the *received*
    plan, so all controllers run the identical install code path;
  * each process computes a digest of the control decision it is about
    to execute (the installed plan PLUS the async-admission merge,
    federated/api._write_ahead_plan) and cross-checks it against the
    other controllers: a diverged process fails loud
    (`PlanDigestError`) instead of silently desyncing;
  * the digest is write-ahead journaled (`schedule` events gain a
    `digest` field, flushed durable BEFORE dispatch), so a plan is
    never executed before it is durable, and a coordinator lost
    mid-run is survivable: ANY process can load the shared checkpoint
    (thr_*/sched_*/smp_* state), be promoted to coordinator, replay
    the deterministic selection stream past the boundary, and verify
    its recomputed digests against the journaled plan stream —
    deterministic takeover, proven bit-exact in
    tests/test_controlplane.py.

Two transports implement the broadcast:

  * `HostCollectiveTransport` — the production path: one
    fixed-size one-to-all host collective per round
    (multihost_utils.broadcast_one_to_all) plus a digest allgather for
    the cross-check. This container cannot run multi-process jax CPU
    (known limitation, CHANGES.md PR 11), so the collective itself is
    exercised only at process_count() == 1; the payload pack/unpack
    and serialization round-trip are unit-tested.
  * `EmulatedPlanNetwork` + `EmulatedTransport` — the primary CI
    surface: N controller objects in ONE process over an in-memory
    bus, with scriptable faults from utils/faults.FaultSchedule —
    `coordinator_crash_at` (the coordinator dies mid-broadcast),
    `broadcast_drop` / `broadcast_dup` / `broadcast_slow` (lost,
    duplicated, late deliveries). Sends and receives ride
    utils/retry.with_retries, so a dropped or slow broadcast is
    ridden out exactly like a coordinator blip on a preemptible pod.

`MirroredControllers` is the emulated multi-controller harness proper:
it drives N `RoundScheduler`s in lockstep the way N SPMD processes
would run the identical sampler code — per-controller rng replicas for
the shared-stream draws, broadcast-received plans for the
process-local ones — and `take_plan` cross-checks every controller's
installed plan byte-for-byte before the model consumes it.
"""
from __future__ import annotations

import hashlib
import json
import os
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from commefficient_tpu.utils.faults import FaultSchedule, InjectedFault
from commefficient_tpu.utils.retry import with_retries

PLAN_WIRE_VERSION = 1

# fixed payload buffer of the production collective: 8-byte length
# header + the serialized plan. One [8 + PLAN_MAX_BYTES] u8 collective
# per round regardless of plan content; a W=4096 cohort's plan is
# ~100 KB of JSON, far under the cap.
PLAN_MAX_BYTES = 1 << 20


class PlanDigestError(RuntimeError):
    """A controller's installed control decision diverged from the
    broadcast plan stream (or from the write-ahead journaled stream on
    a deterministic restart). Always fatal: a silent desync here means
    different processes dispatch different rounds."""


# ---------------------------------------------------------------------------
# serialization: RoundPlan <-> a fixed small host-side payload


def _float_list(arr) -> Optional[List[float]]:
    if arr is None:
        return None
    # float() of an f32 round-trips exactly through JSON (shortest
    # repr), so deserialize(serialize(p)) is BIT-identical — the
    # identity the N-controller bit-exactness tests rest on
    return [float(v) for v in np.asarray(arr, np.float32)]


def _opt_float(v) -> Optional[float]:
    return None if v is None else float(v)


def serialize_plan(plan) -> bytes:
    """One RoundPlan as canonical JSON bytes (sorted keys, compact
    separators) — deterministic, so its sha256 is a well-defined plan
    identity."""
    obj = {
        "v": PLAN_WIRE_VERSION,
        "round": int(plan.round_idx),
        "n_sampled": int(plan.n_sampled),
        "sampler": str(plan.sampler),
        "participants": (None if plan.participants is None
                         else [int(c) for c in
                               np.asarray(plan.participants)]),
        "active": _float_list(plan.active),
        "work": _float_list(plan.work),
        "deadline_s": _opt_float(plan.deadline_s),
        "est_round_s": _opt_float(plan.est_round_s),
        "expected_round_s": _opt_float(plan.expected_round_s),
    }
    if plan.screen_mult is not None:
        # adaptive screening (ISSUE 17): the key is CONDITIONAL so a
        # non-adaptive run's wire bytes (and their sha256 plan
        # identities) stay byte-identical to a pre-17 build
        obj["screen_mult"] = float(np.float32(plan.screen_mult))
    if plan.controls:
        # controller bank (ISSUE 20): conditional for the same
        # reason. Ints (span picks) ride exact; floats are f32-rounded
        # at stamp time and float() round-trips them bit-exactly
        # through JSON, so install == stamp on every controller.
        obj["controls"] = {
            str(k): (int(v) if isinstance(v, (int, np.integer))
                     else float(np.float32(v)))
            for k, v in plan.controls.items()}
    return json.dumps(obj, sort_keys=True,
                      separators=(",", ":")).encode()


def deserialize_plan(payload: bytes):
    """Inverse of serialize_plan; raises PlanDigestError on a payload
    this version cannot faithfully install (wire-version skew is a
    deployment error, not a silent downgrade)."""
    from commefficient_tpu.scheduler import RoundPlan
    obj = json.loads(payload.decode())
    if obj.get("v") != PLAN_WIRE_VERSION:
        raise PlanDigestError(
            f"plan wire version {obj.get('v')!r} != "
            f"{PLAN_WIRE_VERSION} — mixed-build controller fleet")

    def arr(key, dtype):
        v = obj.get(key)
        return None if v is None else np.asarray(v, dtype)

    return RoundPlan(
        int(obj["round"]), int(obj["n_sampled"]),
        arr("active", np.float32), arr("work", np.float32),
        obj.get("deadline_s"), obj.get("est_round_s"),
        obj.get("expected_round_s"), str(obj["sampler"]),
        arr("participants", np.int64),
        screen_mult=obj.get("screen_mult"),
        controls=obj.get("controls"))


def payload_digest(payload: bytes) -> str:
    return hashlib.sha256(payload).hexdigest()


def plan_digest(plan) -> str:
    return payload_digest(serialize_plan(plan))


def install_digest(round_idx: int, client_ids, survivors, work,
                   admits: Sequence = (), poison=None,
                   screen_on=None) -> str:
    """Digest of the control decision a process is about to EXECUTE:
    the post-composition cohort (ids after async admission), the
    survivor/work operands, and the admit merges themselves — the
    plan-carried form of the admission stream. Every controller must
    compute the identical value (transport.verify), and the value is
    write-ahead journaled so a deterministic restart can prove its
    recomputed stream matches the pre-crash run's.

    poison/screen_on (ISSUE 16): a screened-family dispatch folds its
    value-fault mask and its per-round screen-enable decision into the
    digest too, so multi-controller screened runs stay digest-
    consistent (a process whose rollback window diverged fails loud).
    Left at None — every default-family dispatch — the digest bytes
    are identical to the pre-feature build's."""
    obj = {
        "round": int(round_idx),
        "ids": [int(c) for c in np.asarray(client_ids).reshape(-1)],
        "surv": _float_list(survivors),
        "work": _float_list(work),
        "admits": [[int(s), int(c), float(np.float32(f)), int(o)]
                   for (s, c, f, o) in admits],
    }
    if poison is not None or screen_on is not None:
        obj["poison"] = _float_list(poison)
        obj["screen_on"] = (None if screen_on is None
                            else float(np.float32(screen_on)))
    return payload_digest(json.dumps(
        obj, sort_keys=True, separators=(",", ":")).encode())


def journaled_plan_stream(
        journal_path: str) -> Tuple[Dict[int, str], Dict[int, bytes]]:
    """The write-ahead plan stream of an existing run journal, in ONE
    read: ({round_idx: digest}, {round_idx: serialized RoundPlan
    bytes}) from its `schedule` events (later segments override
    earlier ones — a resumed run legitimately re-journals replayed
    rounds). Transport-run journals carry the full plan payload on
    every event, so a long run's journal is large — the
    deterministic-restart path (FedModel.load_plan_stream) needs both
    maps and must not parse the file twice.

    The plan bytes are the AUTHORITATIVE decision log: a restart
    hands them to RoundScheduler.load_replay_plans so replayed rounds
    INSTALL the durably committed decisions instead of recomputing
    them — a throughput selection recomputed against the restored
    tracker would diverge wherever wall-clock EMA feeds landed
    between the checkpoint boundary and the crash. The digests
    cross-check every replayed round's recomputed install digest, so
    a replay that still diverges fails loud."""
    from commefficient_tpu.telemetry.journal import read_journal
    digests: Dict[int, str] = {}
    plans: Dict[int, bytes] = {}
    if not os.path.exists(journal_path):
        return digests, plans
    records, _ = read_journal(journal_path)
    for rec in records:
        if (rec.get("event") != "schedule"
                or not isinstance(rec.get("round"), int)):
            continue
        if isinstance(rec.get("digest"), str):
            digests[rec["round"]] = rec["digest"]
        if isinstance(rec.get("plan"), str):
            plans[rec["round"]] = rec["plan"].encode()
    return digests, plans


def journaled_schedule_digests(journal_path: str) -> Dict[int, str]:
    """{round_idx: digest} of the write-ahead stream (one-map
    convenience over journaled_plan_stream)."""
    return journaled_plan_stream(journal_path)[0]


def journaled_plans(journal_path: str) -> Dict[int, bytes]:
    """{round_idx: plan bytes} of the write-ahead stream (one-map
    convenience over journaled_plan_stream)."""
    return journaled_plan_stream(journal_path)[1]


# ---------------------------------------------------------------------------
# transport interface


class PlanTransport:
    """One-to-all broadcast of serialized RoundPlans plus the
    cross-controller digest check. `broadcast(r, payload)` is called
    with the payload on the coordinator and None elsewhere; every
    caller returns the round's DELIVERED payload (the coordinator
    installs the round-tripped bytes too — identical code path)."""

    @property
    def is_coordinator(self) -> bool:
        raise NotImplementedError

    def broadcast(self, round_idx: int,
                  payload: Optional[bytes] = None) -> bytes:
        raise NotImplementedError

    def verify(self, round_idx: int, digest: str,
               scope: str = "plan") -> None:
        """Cross-check this process's digest against the other
        controllers'; raises PlanDigestError on divergence. Two scopes
        ride the same transport: "plan" (the scheduler's installed
        RoundPlan bytes, checked at install) and "install" (the
        model's executed-decision digest — cohort + operands + admit
        merges — checked at dispatch); they hash different objects, so
        the cross-checks are namespaced per scope."""
        raise NotImplementedError


class HostCollectiveTransport(PlanTransport):
    """Production transport: one fixed-size one-to-all host collective
    per round (the thin DCN-friendly payload the ISSUE specifies) and
    a digest allgather for verify. Degenerates to the identity at
    process_count() == 1 — which is all this container can execute
    (multi-process jax CPU is unavailable here), so the collective
    path is serialization-unit-tested while the emulated harness is
    the CI surface for the fault story."""

    def __init__(self, max_bytes: int = PLAN_MAX_BYTES):
        self.max_bytes = int(max_bytes)

    @property
    def is_coordinator(self) -> bool:
        from commefficient_tpu.parallel import multihost as mh
        return mh.is_coordinator()

    def pack(self, payload: Optional[bytes]) -> np.ndarray:
        """[8 + max_bytes] u8 buffer: little-endian length header +
        payload; non-coordinators contribute zeros (ignored by the
        one-to-all collective)."""
        buf = np.zeros(8 + self.max_bytes, np.uint8)
        if payload is not None:
            if len(payload) > self.max_bytes:
                raise ValueError(
                    f"serialized plan is {len(payload)} bytes > "
                    f"transport max {self.max_bytes}")
            buf[:8] = np.frombuffer(
                len(payload).to_bytes(8, "little"), np.uint8)
            buf[8:8 + len(payload)] = np.frombuffer(payload, np.uint8)
        return buf

    @staticmethod
    def unpack(buf: np.ndarray) -> bytes:
        buf = np.asarray(buf, np.uint8)
        n = int.from_bytes(buf[:8].tobytes(), "little")
        return buf[8:8 + n].tobytes()

    def broadcast(self, round_idx: int,
                  payload: Optional[bytes] = None) -> bytes:
        from jax.experimental import multihost_utils

        def send():
            out = multihost_utils.broadcast_one_to_all(
                self.pack(payload))
            return self.unpack(np.asarray(out))

        # transient coordination blips (a neighbor host restarting)
        # retry symmetrically on every process — the same failure is
        # visible fleet-wide, so the retrying collective re-forms
        return with_retries(
            send, describe=f"round {round_idx} plan broadcast")

    def verify(self, round_idx: int, digest: str,
               scope: str = "plan") -> None:
        from commefficient_tpu.parallel import multihost as mh
        if not mh.is_multihost():
            return
        from jax.experimental import multihost_utils
        mine = np.frombuffer(bytes.fromhex(digest), np.uint8)
        all_d = np.asarray(
            multihost_utils.process_allgather(mine))
        if not (all_d == mine[None, :]).all():
            bad = [p for p in range(all_d.shape[0])
                   if not (all_d[p] == mine).all()]
            raise PlanDigestError(
                f"round {round_idx}: {scope} digest diverged across "
                f"controllers (processes {bad} disagree with "
                f"process {mh.process_index()})")


# ---------------------------------------------------------------------------
# emulated multi-controller harness (the primary CI surface)


class EmulatedPlanNetwork:
    """In-memory broadcast bus for N in-process controllers, with the
    FaultSchedule's control-plane faults scripted in: dropped first
    sends, duplicated deliveries, slow receives, and the coordinator
    dying mid-broadcast. `promote` is the deterministic takeover:
    after a coordinator loss the lowest surviving controller id
    becomes the coordinator."""

    def __init__(self, num_controllers: int,
                 schedule: Optional[FaultSchedule] = None):
        if num_controllers < 1:
            raise ValueError("need at least one controller")
        self.num = int(num_controllers)
        self.schedule = schedule
        self.coordinator_id = 0
        self.dead: set = set()
        self._mail: Dict[int, bytes] = {}
        self._send_attempts: Dict[int, int] = {}
        self._recv_attempts: Dict[Tuple[int, int], int] = {}
        # round -> delivery count (2 under broadcast_dup — receivers
        # must install idempotently; tests read this)
        self.deliveries: Dict[int, int] = {}
        # (round, scope) -> {pid: digest} cross-check registrations
        self._digests: Dict[Tuple[int, str], Dict[int, str]] = {}

    def promote(self, pid: Optional[int] = None) -> int:
        """Deterministic takeover after a coordinator loss: mark the
        old coordinator dead and promote `pid` (default: the lowest
        surviving controller id). Returns the new coordinator id."""
        self.dead.add(self.coordinator_id)
        if pid is None:
            pid = min(p for p in range(self.num)
                      if p not in self.dead)
        if pid in self.dead:
            raise ValueError(f"controller {pid} is dead")
        self.coordinator_id = int(pid)
        return self.coordinator_id

    # -- bus primitives (EmulatedTransport drives these) -------------------
    def send(self, round_idx: int, payload: bytes) -> None:
        att = self._send_attempts.get(round_idx, 0)
        self._send_attempts[round_idx] = att + 1
        s = self.schedule
        if s is not None and s.broadcast_dropped(round_idx, att):
            raise TimeoutError(
                f"round {round_idx} plan broadcast lost in flight "
                "(scripted drop)")
        copies = 2 if (s is not None
                       and s.broadcast_duplicated(round_idx)) else 1
        self._mail[round_idx] = payload
        self.deliveries[round_idx] = (
            self.deliveries.get(round_idx, 0) + copies)

    def recv(self, round_idx: int, pid: int) -> bytes:
        key = (round_idx, pid)
        att = self._recv_attempts.get(key, 0)
        self._recv_attempts[key] = att + 1
        s = self.schedule
        if s is not None and att < s.broadcast_slow_attempts(round_idx):
            raise TimeoutError(
                f"round {round_idx} plan not yet visible to "
                f"controller {pid} (scripted slow broadcast)")
        payload = self._mail.get(round_idx)
        if payload is None:
            raise TimeoutError(
                f"round {round_idx} plan not yet broadcast")
        return payload

    def register_digest(self, round_idx: int, pid: int,
                        digest: str, scope: str = "plan") -> None:
        seen = self._digests.setdefault((round_idx, scope), {})
        for other, d in seen.items():
            if d != digest:
                raise PlanDigestError(
                    f"round {round_idx}: controller {pid} installed "
                    f"{scope} digest {digest[:12]}… but controller "
                    f"{other} installed {d[:12]}… — control plane "
                    "diverged")
        seen[pid] = digest


class EmulatedTransport(PlanTransport):
    """One controller's endpoint on an EmulatedPlanNetwork. Sends and
    receives ride utils/retry.with_retries (no real sleeping by
    default — the bus is in-process), so the scripted drop/slow faults
    exercise exactly the retry machinery a pod deployment leans on."""

    def __init__(self, network: EmulatedPlanNetwork, process_id: int,
                 retries: int = 8, retry_sleep=None):
        self.network = network
        self.pid = int(process_id)
        self.retries = int(retries)
        self._sleep = retry_sleep if retry_sleep is not None \
            else (lambda s: None)

    @property
    def is_coordinator(self) -> bool:
        return self.pid == self.network.coordinator_id

    def broadcast(self, round_idx: int,
                  payload: Optional[bytes] = None) -> bytes:
        if self.pid in self.network.dead:
            raise RuntimeError(f"controller {self.pid} is dead")
        if self.is_coordinator and payload is not None:
            s = self.network.schedule
            if s is not None and s.should_crash_coordinator(round_idx):
                # the coordinator dies mid-broadcast: the plan never
                # reaches the bus (it may already be write-ahead
                # journaled — the restart path recomputes and
                # digest-checks it)
                self.network.dead.add(self.pid)
                raise InjectedFault(round_idx - 1)
            with_retries(
                lambda: self.network.send(round_idx, payload),
                retries=self.retries, base_delay=0.0,
                sleep=self._sleep,
                describe=f"round {round_idx} plan broadcast")
        return with_retries(
            lambda: self.network.recv(round_idx, self.pid),
            retries=self.retries, base_delay=0.0, sleep=self._sleep,
            describe=f"round {round_idx} plan receive")

    def verify(self, round_idx: int, digest: str,
               scope: str = "plan") -> None:
        self.network.register_digest(round_idx, self.pid, digest,
                                     scope)


class MirroredControllers:
    """N RoundSchedulers driven in lockstep over one emulated network:
    the in-process stand-in for N SPMD processes running the identical
    sampler code. Duck-types the RoundScheduler surface the FedSampler
    and FedModel consume.

    Per round the coordinator runs first (it owns the live tracker
    and broadcasts at commit_round), then every follower runs the SAME
    select/commit pair with the SAME data-layer inputs — shared-stream
    rng draws replayed from a per-controller rng replica (each real
    process owns an identically-seeded RandomState), process-local
    draws replaced by the broadcast plan. Follower calls are DEFERRED
    to commit time: a real follower process blocks in recv until the
    coordinator's broadcast lands, and in a single-process lockstep
    that ordering is realized by running the followers' select after
    the coordinator's commit has filled the bus. Every follower's
    selection must match the coordinator's, and `take_plan` pops every
    controller's installed plan and cross-checks them byte-for-byte
    (serialize_plan) before the model consumes the coordinator's — a
    diverged controller fails loud either way."""

    def __init__(self, schedulers: List, transports: List,
                 coordinator: int = 0):
        if len(schedulers) != len(transports):
            raise ValueError("one transport per controller")
        self.schedulers = list(schedulers)
        self.transports = list(transports)
        self.coordinator = int(coordinator)
        self._pending_select = None
        self._pending_chosen = None

    @property
    def _coord(self):
        return self.schedulers[self.coordinator]

    @property
    def _followers(self):
        return [(pid, s) for pid, s in enumerate(self.schedulers)
                if pid != self.coordinator
                and pid not in self.transports[pid].network.dead]

    # ---------------- RoundScheduler surface ------------------------------
    @property
    def cfg(self):
        return self._coord.cfg

    @property
    def is_default(self) -> bool:
        return self._coord.is_default

    @property
    def tracker(self):
        return self._coord.tracker

    @property
    def state_prefetch(self):
        return self._coord.state_prefetch

    @state_prefetch.setter
    def state_prefetch(self, fn) -> None:
        self._coord.state_prefetch = fn

    @property
    def screen_ctl(self):
        return self._coord.screen_ctl

    @screen_ctl.setter
    def screen_ctl(self, ctl) -> None:
        # adaptive screening (ISSUE 17): every controller carries the
        # reference — the coordinator stamps plans from it, and a
        # follower's is_default must go False so it installs the
        # broadcast plan instead of skipping commit. Only the model
        # ever calls observe(), so sharing one instance is safe.
        for s in self.schedulers:
            s.screen_ctl = ctl

    @property
    def control_bank(self):
        return self._coord.control_bank

    @control_bank.setter
    def control_bank(self, bank) -> None:
        # controller bank (ISSUE 20): same sharing contract as
        # screen_ctl — the coordinator stamps plans through the bank,
        # followers' is_default goes False so they install the
        # broadcast. Only the model feeds observations and drains
        # adjustment events, so one shared instance is safe here too.
        for s in self.schedulers:
            s.control_bank = bank

    def begin_epoch(self, first_round: int) -> None:
        self._pending_select = None
        for s in self.schedulers:
            s.begin_epoch(first_round)

    def select(self, alive, num_slots: int, rng) -> np.ndarray:
        # coordinator only; the followers' identical select runs at
        # commit time, once the broadcast their recv blocks on has
        # landed. Each real process draws from its OWN
        # identically-seeded RandomState — the stashed rng state
        # replays that per follower, so a shared-stream (uniform) draw
        # advances every controller's rng in lockstep.
        self._pending_select = (np.array(alive, copy=True),
                                int(num_slots), rng.get_state())
        out = self._coord.select(alive, num_slots, rng)
        self._pending_chosen = np.array(out, copy=True)
        return out

    def commit_round(self, client_ids, examples_per_slot) -> None:
        self._coord.commit_round(client_ids, examples_per_slot)
        pending = getattr(self, "_pending_select", None)
        for pid, s in self._followers:
            if pending is not None:
                alive, num_slots, rng_state = pending
                frng = np.random.RandomState()
                frng.set_state(rng_state)
                theirs = np.asarray(s.select(alive, num_slots, frng))
                if not np.array_equal(self._pending_chosen, theirs):
                    raise PlanDigestError(
                        f"controller {pid} selected a different "
                        "cohort than the coordinator at round "
                        f"{s._next_round}")
                fs = self.transports[pid].network.schedule
                if (fs is not None
                        and fs.broadcast_duplicated(s._next_round)):
                    # the duplicated delivery lands AGAIN between the
                    # follower's receive and its commit — the receiver
                    # must install idempotently (same plan, same round
                    # key, counters advanced once)
                    s._recv_plan(s._next_round)
            s.commit_round(client_ids, examples_per_slot)
        self._pending_select = None

    def take_plan(self, round_idx: int):
        plan = self._coord.take_plan(round_idx)
        ref = None if plan is None else serialize_plan(plan)
        for pid, s in self._followers:
            theirs = s.take_plan(round_idx)
            enc = None if theirs is None else serialize_plan(theirs)
            if enc != ref:
                raise PlanDigestError(
                    f"round {round_idx}: controller {pid} installed "
                    "a different plan than the coordinator")
        return plan

    def state_dict(self) -> dict:
        return self._coord.state_dict()

    def load_state_dict(self, state: dict) -> None:
        # the durable state is shared storage: every surviving
        # controller restores the same bytes (how a promoted follower
        # inherits the coordinator's counters/tracker-side state)
        for s in self.schedulers:
            s.load_state_dict(state)

    def load_replay_plans(self, plans: Dict[int, bytes]) -> None:
        # replay bytes install on the COORDINATOR only: it
        # rebroadcasts them verbatim, and the followers receive the
        # journaled stream exactly like live rounds
        self._coord.load_replay_plans(plans)


def attach_emulated_cluster(model, train_loader,
                            num_controllers: int = 2,
                            coordinator: int = 0,
                            schedule: Optional[FaultSchedule] = None,
                            network: Optional[
                                EmulatedPlanNetwork] = None):
    """Test/driver wiring of the emulated multi-controller harness:
    builds N RoundSchedulers — the coordinator over the model's live
    throughput tracker, followers over their own (deliberately
    divergent: never fed) trackers, so any follower decision that
    leaks local state fails the cross-checks — attaches their
    transports, installs the MirroredControllers facade as the run's
    scheduler, and points the model at the coordinator's transport
    for install-digest verification. Returns (mirror, network).

    Pass an existing `network` (with `promote()` already applied) to
    model a deterministic takeover: the promoted controller becomes
    the broadcaster while the dead one is excluded from lockstep."""
    from commefficient_tpu.scheduler import RoundScheduler
    from commefficient_tpu.telemetry.clients import (
        ClientThroughputTracker,
    )
    if network is None:
        network = EmulatedPlanNetwork(num_controllers,
                                      schedule=schedule)
        network.coordinator_id = int(coordinator)
    coordinator = network.coordinator_id
    scheds, transports = [], []
    for pid in range(network.num):
        tracker = (model.throughput if pid == coordinator
                   else ClientThroughputTracker(model.num_clients))
        s = RoundScheduler(model.cfg, model.num_clients, tracker)
        t = EmulatedTransport(network, pid)
        s.attach_transport(t)
        scheds.append(s)
        transports.append(t)
    mirror = MirroredControllers(scheds, transports,
                                 coordinator=coordinator)
    train_loader.sampler.scheduler = mirror
    model.attach_scheduler(mirror)
    model.attach_data_sampler(train_loader.sampler)
    model.attach_transport(transports[coordinator])
    return mirror, network


def attach_config_transport(model, train_loader, cfg):
    """Driver wiring for Config.plan_transport (both drivers call this
    right after scheduler.attach_round_scheduler, BEFORE --resume):

      * "collective" — attach the production HostCollectiveTransport
        to the run's single RoundScheduler (multi-controller SPMD: the
        real processes each run this same line);
      * "emulated"  — replace the scheduler with an in-process
        N-controller MirroredControllers harness
        (cfg.plan_controllers controllers). Chaos scripting rides env
        vars so the production CLI stays clean:
        CCTPU_EMU_COORD_CRASH=<round> kills the coordinator
        mid-broadcast of that round (a scripted crash),
        CCTPU_EMU_COORDINATOR=<pid> picks the (takeover)
        coordinator id.

    Returns the attached transport/mirror, or None when
    cfg.plan_transport is empty."""
    if not cfg.plan_transport:
        return None
    if cfg.plan_transport == "collective":
        t = HostCollectiveTransport()
        model.scheduler.attach_transport(t)
        model.attach_transport(t)
        return t
    schedule = None
    crash = os.environ.get("CCTPU_EMU_COORD_CRASH", "")
    if crash:
        schedule = FaultSchedule(coordinator_crash_at=int(crash))
    coordinator = int(os.environ.get("CCTPU_EMU_COORDINATOR", "0"))
    mirror, _ = attach_emulated_cluster(
        model, train_loader,
        num_controllers=int(cfg.plan_controllers),
        coordinator=coordinator, schedule=schedule)
    return mirror

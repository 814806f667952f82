"""Reference-shaped high-level API: FedModel + FedOptimizer.

Drop-in call-pattern parity with the reference driver loop (reference:
CommEfficient/cv_train.py:389-404, 193-229):

    model = FedModel(module, compute_loss_train, cfg, compute_loss_val)
    opt = FedOptimizer(model, cfg)
    scheduler = LambdaLR(opt, lr_lambda=...)
    ...
    scheduler.step()
    loss, acc, download, upload = model(batch)   # one federated round
    opt.step()
    ...
    model.finalize()

Under the hood there are no processes, queues, or shared memory
(reference FedModel.__init__ spawns workers and a NCCL group,
fed_aggregator.py:137-164): the entire round — client compute, psum,
server decompression, weight update, client-state scatter — is ONE
jitted program built by `federated.round.make_round_fns`, executed when
`model(batch)` is called. The learning rate the scheduler set *before*
the call is the one the fused round applies, which matches the
reference's ordering (lr_scheduler.step() precedes model(batch),
cv_train.py:198-229); `opt.step()` therefore only performs host-side
bookkeeping and exists for call-pattern parity.

The loss callback contract is preserved from the reference
(SURVEY.md §3.5) modulo functional style: the reference takes
compute_loss(model, batch, args) -> (loss, *metrics); here it is
loss_fn(params_pytree, batch_tuple, mask) -> (loss, (metrics...)) —
the mask is the price of static shapes.
"""
from __future__ import annotations

import time
from typing import Callable, List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from commefficient_tpu.config import Config
from commefficient_tpu.federated import round as fround
from commefficient_tpu.federated.accounting import (
    CommAccountant, pack_change_bits,
)
from commefficient_tpu.federated.async_agg import AsyncAdmitBuffer
from commefficient_tpu.ops.flat import flatten_params
from commefficient_tpu.parallel import multihost as mh
from commefficient_tpu.parallel.mesh import make_multihost_client_mesh
from commefficient_tpu.parallel.plantransport import (
    PlanDigestError, install_digest,
)
from commefficient_tpu.telemetry.clients import ClientThroughputTracker
from commefficient_tpu.telemetry.metrics import METRIC_INDEX
from commefficient_tpu.telemetry.trace import TRACE
from commefficient_tpu.utils.faults import (
    FaultSchedule, InjectedFault, bernoulli_survivors, byzantine_mask,
    poison_mask, straggler_work_fractions,
)
from commefficient_tpu.utils.retry import is_transient_error, with_retries


class _StagedRound(NamedTuple):
    """One round's host-prepared dispatch operands (FedModel.
    stage_round): the batch leaves already explicitly placed on the
    mesh, plus the host-side copies commit_staged's accounting and
    telemetry consume. Staging may run one round AHEAD of the commit
    (the pipelined prefetch) because nothing in it reads round
    state — fault draws are pure functions of (seed, round index)."""
    round_idx: int
    batch: "fround.RoundBatch"        # operands placed on the mesh
    lr: jax.Array
    client_ids: np.ndarray            # host copy, post-admission
    survivors: Optional[np.ndarray]   # host copy (accounting)
    # tiered client state (ISSUE 11, Config.state_tier=host): the
    # round's LRU slot assignment + spill/restore motion, decided at
    # stage time (pure host bookkeeping, deterministic in the cohort
    # stream) and executed against the device block at commit time.
    # None under the default device tier. When set, `batch.client_ids`
    # carries device SLOTS, not global ids — the gather/scatter
    # programs index the bounded working-set block.
    tier_plan: Optional[object] = None


class _SpanHandle(NamedTuple):
    """One dispatched-but-uncollected scanned span (FedModel.
    dispatch_rounds -> collect_rounds). `metrics`/`bits` are the span
    program's output futures; the host copies carry what the deferred
    accounting/telemetry commit needs. Collect in dispatch order."""
    first: int
    ids_host: np.ndarray              # [N, W], post-admission
    surv_all: Optional[np.ndarray]
    work_all: Optional[np.ndarray]
    crash_at: Optional[int]
    account: bool
    metrics: object                   # round.RoundMetrics (futures)
    bits: jax.Array                   # [N, D/32] change bitsets
    t_dispatch0: float
    t_dispatched: float
    # graftscope correlation (ISSUE 13): the scanned-span index at
    # dispatch — the same counter --profile_spans selects on, so the
    # device_execute trace span recorded at collect correlates with a
    # jax.profiler capture of the same span. -1 = unknown (callers
    # outside the scanloop).
    span_idx: int = -1


class FedModel:
    def __init__(self, module, loss_train, cfg: Config,
                 loss_val=None, params=None, mesh=None,
                 init_batch=None, num_clients: Optional[int] = None,
                 lr_scale_vec: Optional[np.ndarray] = None):
        """module: a Flax module (init/apply) OR None if `params` and
        loss callbacks close over the model themselves.
        loss_*: loss_fn(params, batch_tuple, mask) -> (loss, metrics).
        init_batch: example batch tuple for module.init.
        """
        self.module = module
        self.training = True
        if params is None:
            if module is None or init_batch is None:
                raise ValueError("need either params or module+init_batch")
            params = module.init(jax.random.PRNGKey(cfg.seed), *init_batch)
        # shapes only: the tree itself is one more copy of the model
        # on the device for as long as this object lives
        self.params_template = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(jnp.shape(x),
                                           jnp.result_type(x)), params)
        vec, self.unravel = flatten_params(params)
        del params
        cfg = cfg.replace(grad_size=int(vec.shape[0])).validate()
        self.cfg = cfg

        if mesh is None:
            # widest clients axis that divides num_workers (round_step
            # shards the participating clients evenly across the mesh),
            # after reserving the model_parallel factor: with mp > 1
            # the mesh carries a model axis (the engine replicates over
            # it unless the loss is tp-wrapped, parallel/tp.py — see
            # gpt2_train's TP branch for the wrapped path)
            mp = max(cfg.model_parallel, 1)
            if len(jax.devices()) < mp:
                raise ValueError(
                    f"model_parallel={mp} needs at least {mp} devices, "
                    f"have {len(jax.devices())}")
            n = min(len(jax.devices()) // mp, max(cfg.num_workers, 1))
            while cfg.num_workers % n:
                n -= 1
            # slice-major DCN layout: real multi-slice topology is
            # auto-detected; --num_slices > 1 emulates the grouping on
            # single-slice/CPU devices (and on real multi-slice
            # hardware must match the physical count); the flat
            # single-slice mesh is the default case of the same call.
            # The device subset is chosen slice-balanced: a flat
            # prefix of jax.devices() can land unevenly across slices
            # (4+2 of 2x4) and the hybrid mesh build would fail; when
            # no balanced pick exists, fall back to a flat mesh over
            # the prefix.
            from commefficient_tpu.parallel.mesh import (
                make_client_mesh, make_client_model_mesh,
                slice_balanced_prefix,
            )
            picked = slice_balanced_prefix(jax.devices(), n * mp)
            if picked is not None:
                mesh = make_multihost_client_mesh(
                    model_parallel=mp, devices=picked,
                    num_slices=cfg.num_slices if cfg.num_slices > 1
                    else None)
            elif mp == 1:
                mesh = make_client_mesh(n)
            else:
                mesh = make_client_model_mesh(
                    n, mp, devices=jax.devices()[:n * mp])
        self.mesh = mesh
        self.num_clients = cfg.resolved_num_clients(num_clients)

        self._loss_train = loss_train
        self._loss_val = loss_val if loss_val is not None else loss_train

        # frozen-coordinate gradient mask: exactly-zero lr scales mark
        # finetune-frozen leaves; zero their gradients at the source so
        # they consume no compression budget (reference freezing is
        # requires_grad=False, which removes them entirely)
        grad_mask = None
        if lr_scale_vec is not None and np.any(np.asarray(lr_scale_vec) == 0):
            grad_mask = (np.asarray(lr_scale_vec) != 0).astype(np.float32)

        self._train_round = fround.make_train_fn(
            self._loss_train, self.unravel, cfg, self.mesh,
            grad_mask=grad_mask)
        self._eval_batch = fround.make_eval_fn(
            self._loss_val, self.unravel, cfg, self.mesh)

        self.server = fround.init_server_state(cfg, vec, mesh=self.mesh)
        # tiered cold client state (ISSUE 11): under state_tier=host
        # the ClientState blocks hold only the LRU working set —
        # client_state_rows picks the allocation — and the store below
        # conducts slot assignment, host spill, and restore through
        # the SAME gather/scatter state-motion programs.
        self.clients = fround.init_client_state(
            cfg, fround.client_state_rows(cfg, self.num_clients), vec,
            mesh=self.mesh)
        self.state_store = None
        if cfg.state_tier != "device":
            from commefficient_tpu.federated.statestore import (
                TieredStateStore, tracked_fields,
            )
            if any(tracked_fields(cfg).values()):
                self.state_store = TieredStateStore(
                    cfg, self.mesh, self._train_round, vec,
                    self.num_clients)
        # O(cohort) checkpointing (ISSUE 9): client-state rows are zero
        # (or the init-weights tile, topk_down) until a client first
        # participates, so checkpoints persist only the rows of
        # clients-ever-sampled — this host-side id set tracks them.
        # The init-weights host copy reconstructs untouched topk_down
        # rows at resume. A resume from a LEGACY dense checkpoint loses
        # the touched set, so saves fall back to dense from there on
        # (_sparse_rows_ok).
        self._touched: set = set()
        self._sparse_rows_ok = True
        self._init_weights_host = (np.asarray(vec, np.float32)
                                   if cfg.do_topk_down else None)

        self.accountant = CommAccountant(
            cfg, self.num_clients,
            frozen_count=(0 if grad_mask is None
                          else int((grad_mask == 0).sum())))
        # differential privacy (ISSUE 19): the RDP accountant is
        # stateless — cumulative epsilon is a pure function of the
        # committed-round count, so a crash->resume re-derives the
        # identical curve from the restored round counter (no extra
        # checkpoint state, no drift).
        self.privacy = None
        if cfg.mode == "dp_sketch" and cfg.dp_noise_mult > 0:
            from commefficient_tpu.compress import RdpAccountant
            self.privacy = RdpAccountant(cfg.dp_noise_mult,
                                         cfg.dp_delta)
        self._prev_change_words: Optional[np.ndarray] = None
        self._pack_bits = jax.jit(pack_change_bits)
        from jax.sharding import PartitionSpec as P
        self._P = P
        # the PRNG key (like every jit operand) must be a GLOBAL array
        # in a multi-controller run; globalize is a plain device_put in
        # single-process ones
        self._key = mh.globalize(self.mesh, P(),
                                 jax.random.PRNGKey(cfg.seed))
        self._optimizer: Optional["FedOptimizer"] = None
        # per-parameter lr scale vector (Fixup param groups,
        # reference fed_aggregator.py:411-427); None -> scalar lr.
        # Held host-side: the product with the scheduler's lr is formed
        # on host and globalized per call.
        self.lr_scale_vec = (None if lr_scale_vec is None
                             else np.asarray(lr_scale_vec, np.float32))
        # global-feeding fallback for meshes where a process's devices
        # are NOT a contiguous block of the clients axis (multihost.
        # local_row_slice raises there): every process materializes the
        # identical GLOBAL batch and it is placed per-shard via
        # globalize's callback — correct for any device->process
        # layout, at the cost of host-materializing the full batch.
        self.feed_global = False
        # fault tolerance: host-side mirror of server.round_idx (kept
        # in lockstep so survivor draws and crash points never sync on
        # the device counter), plus an optional injected fault script
        # (utils/faults.FaultSchedule; set_fault_schedule)
        self._rounds_done = 0
        self.fault_schedule: Optional[FaultSchedule] = None
        # finite-frontier rollback (ISSUE 16): rounds below this index
        # dispatch with the admission screen FORCE-enabled — set by
        # force_screen_rounds after a numeric-trip rollback so the
        # replayed window screens the corruption out. 0 = no window.
        self._screen_force_until = 0
        # adaptive screening (ISSUE 17): one controller per run tunes
        # the norm-screen multiplier toward --target_screened_rate;
        # attach_scheduler shares it with the RoundScheduler so the
        # live value rides every sealed plan. _plan_screen_mult stashes
        # a consumed plan's stamped multiplier per round — a replayed
        # or broadcast plan's value WINS over the local controller's.
        self.screen_ctl = None
        if cfg.adaptive_screen:
            from commefficient_tpu.scheduler import (
                AdaptiveScreenController,
            )
            self.screen_ctl = AdaptiveScreenController(cfg)
        self._plan_screen_mult = {}
        # plan-riding controller bank (ISSUE 20, control/): None by
        # default — make_bank constructs one exactly when a bank
        # controller flag is set. attach_scheduler shares it so the
        # fresh coordinator path stamps every sealed plan through it;
        # _plan_controls stashes each consumed plan's `controls` dict
        # per round — the plan-carried values WIN over local state
        # (install) and the stashed staleness_decay is applied to the
        # async admission buffer at compose time, so the discount a
        # round executes with is exactly the digest-covered journaled
        # one.
        from commefficient_tpu.control import make_bank
        self.control_bank = make_bank(cfg)
        self._plan_controls = {}
        # observability (telemetry/): the throughput tracker always
        # exists (cheap arrays; its state rides in every checkpoint so
        # resume restores it even for runs that never journal), while
        # the session — journal + profiler + the host-side metric
        # conductor — is attached by the driver when cfg.telemetry is on
        self.throughput = ClientThroughputTracker(self.num_clients)
        self.telemetry = None
        # round scheduling (commefficient_tpu/scheduler): the drivers
        # attach a RoundScheduler whose selection-time plans this
        # model consumes at dispatch (attach_scheduler); None — or a
        # default uniform/no-deadline scheduler, which plans nothing —
        # leaves every code path bit-identical to a scheduler-free
        # build
        self.scheduler = None
        # the run's FedSampler (data/sampler.py), attached by
        # scheduler.attach_round_scheduler so its stream state rides
        # in checkpoints (smp_* keys) — the exact-data-stream resume
        # contract under non-uniform sampling
        self.data_sampler = None
        # per-round scheduled-slot masks (RoundPlan.active), stashed
        # at plan consumption and handed to the telemetry feeding so
        # idle over-provisioned pads are EXCLUDED from the throughput
        # tracker (they were never asked to work — counting them as
        # participations would depress the completion ratio the
        # scheduler's survival estimate reads)
        self._plan_active = {}
        # coordinator-broadcast control plane (ISSUE 12,
        # parallel/plantransport.py). plan_transport: the attached
        # PlanTransport (None = transport-free, every path
        # bit-identical to the pre-feature build). _plan_journal:
        # consumed plans' journal fields, stashed by _faults_for_round
        # and sealed WRITE-AHEAD by _seal_plan — the `schedule` event
        # (with its install digest) is journaled and flushed durable
        # BEFORE the round's dispatch, so a plan is never executed
        # before it is durable. _replay_digests: the write-ahead plan
        # stream of a pre-crash journal (load_plan_stream) — a
        # deterministic restart cross-checks every replayed round's
        # recomputed digest against it and fails loud on divergence.
        self.plan_transport = None
        self._plan_journal = {}
        self._replay_digests = {}
        self._wa_dirty = False
        # pipelined round engine (ISSUE 10): stage-side round counter
        # (runs ahead of _rounds_done when a prefetched round/span has
        # been staged but not yet committed; equal otherwise), the
        # buffered async-admission state (--async_admit_rounds), and
        # the off-critical-path checkpoint writer (--pipeline). All
        # three are None/identity in the default config, so the
        # default dispatch path is bit-identical to the pre-feature
        # synchronous loop.
        self._rounds_staged = 0
        self.async_admit = (
            AsyncAdmitBuffer(cfg.async_admit_rounds,
                             cfg.async_staleness_decay)
            if cfg.async_admit_rounds > 0 else None)
        if cfg.pipeline:
            # deferred import: utils.checkpoint imports federated.round
            # for its (Server|Client)State types, so a module-level
            # import here would be circular
            from commefficient_tpu.utils.checkpoint import (
                AsyncCheckpointWriter,
            )
            self.ckpt_writer = AsyncCheckpointWriter(
                drain_timeout=cfg.writer_drain_timeout_s)
        else:
            self.ckpt_writer = None

    def attach_telemetry(self, session) -> None:
        """Install a telemetry.TelemetrySession (or None to detach).
        The model feeds it per-round device metric vectors on the
        unscanned path (one-round lag — no added syncs) and whole
        host-materialized spans from run_rounds; a session without its
        own tracker is pointed at this model's `throughput`."""
        self.telemetry = session
        if session is not None and session.tracker is None:
            session.tracker = self.throughput

    def attach_scheduler(self, scheduler) -> None:
        """Install a scheduler.RoundScheduler (or None to detach). Its
        per-round plans — idle over-provisioned slots and deadline
        work fractions — compose into the fault operands in
        _faults_for_round; scheduler state rides in checkpoints under
        `sched_*` keys and load_state restores it."""
        self.scheduler = scheduler
        if scheduler is not None:
            # working-set-aware prefetch (ISSUE 11): the scheduler's
            # commit_round warms the HOST side of an upcoming plan's
            # cohort restores — LRU-neutral, so prefetch timing can
            # never perturb the eviction stream
            scheduler.state_prefetch = (
                self.state_store.prefetch_host_rows
                if self.state_store is not None else None)
            # adaptive screening (ISSUE 17): the scheduler stamps the
            # controller's live multiplier into every sealed plan (and
            # its is_default goes False, so plans exist to carry it)
            if self.screen_ctl is not None:
                scheduler.screen_ctl = self.screen_ctl
            # controller bank (ISSUE 20): same sharing contract — the
            # scheduler stamps fresh plans through the bank and its
            # is_default goes False so plans exist to carry the values
            if self.control_bank is not None:
                scheduler.control_bank = self.control_bank

    def scheduler_state(self) -> Optional[dict]:
        """The `sched_*` checkpoint payload: the attached scheduler's
        counter state_dict, or None without one — every checkpoint
        call site passes this, next to throughput.state_dict()."""
        return (self.scheduler.state_dict()
                if self.scheduler is not None else None)

    def attach_data_sampler(self, sampler) -> None:
        """Install the run's FedSampler (or None to detach). Its
        stream state — rng, mid-epoch cursor and permutations — rides
        in checkpoints under `smp_*` and load_state restores it, so a
        resumed run CONTINUES the exact data stream rather than
        replaying the epoch head (which, under non-uniform sampling,
        would re-draw against the checkpoint-time tracker and feed
        different data than the uninterrupted run)."""
        self.data_sampler = sampler

    def sampler_state(self) -> Optional[dict]:
        """The `smp_*` checkpoint payload: the attached FedSampler's
        stream state_dict, or None without one."""
        return (self.data_sampler.state_dict()
                if self.data_sampler is not None else None)

    def async_admit_state(self) -> Optional[dict]:
        """The `asyb_*` checkpoint payload: pending async-admission
        entries (federated/async_agg), or None when buffered async
        aggregation is off — every checkpoint call site passes this
        next to sampler_state()."""
        return (self.async_admit.state_dict()
                if self.async_admit is not None else None)

    def attach_transport(self, transport) -> None:
        """Install a parallel/plantransport.PlanTransport (or None to
        detach). With one attached, every round's control decision —
        the post-composition cohort, survivor/work operands, and async
        admit merges — is digested, write-ahead journaled (`schedule`
        events gain a `digest` field, flushed durable before
        dispatch), and cross-checked against the other controllers
        (transport.verify); a diverged process raises PlanDigestError
        instead of silently dispatching a different round."""
        self.plan_transport = transport

    def load_plan_stream(self, journal_path: str) -> None:
        """Deterministic-restart hook: load the write-ahead plan
        stream of the pre-crash run. Two halves:

          * the journaled PLANS install into the scheduler
            (load_replay_plans) — replayed rounds re-execute the
            exact decisions the crashed run durably committed (the
            journal is the AUTHORITATIVE decision log; recomputing a
            throughput selection against the restored tracker would
            diverge wherever wall-clock EMA feeds landed between the
            checkpoint boundary and the crash);
          * the journaled DIGESTS cross-check every replayed round's
            recomputed install digest — a replay that still diverges
            (differing seed/config, a non-deterministic merge) fails
            loud (PlanDigestError) instead of silently rewriting
            history."""
        from commefficient_tpu.parallel.plantransport import (
            journaled_plan_stream,
        )
        self._replay_digests, plans = journaled_plan_stream(
            journal_path)
        if plans and self.scheduler is not None and hasattr(
                self.scheduler, "load_replay_plans"):
            self.scheduler.load_replay_plans(plans)

    def _seal_plan(self, round_idx: int, client_ids,
                   survivors, work, admits=(), pois=None,
                   screen=None) -> None:
        """Write-ahead seal of one round's control decision (ISSUE
        12): journal the `schedule` event (with the install digest
        when a transport or a replay stream is live), cross-check the
        digest against the replayed journal and the other
        controllers. Transport-free default runs with a default
        scheduler stash no fields and compute no digest — this is a
        no-op there, bit-identically to the pre-feature build.

        pois/screen (ISSUE 16): a screened-family round's poison mask
        and screen-enable flag are part of the control decision — they
        ride the digest and the journaled record, so multi-controller
        screened runs verify them like any other operand and a replay
        with a diverged rollback window fails loud."""
        fields = self._plan_journal.pop(int(round_idx), None)
        digest = None
        if self.plan_transport is not None or self._replay_digests:
            digest = install_digest(round_idx, client_ids, survivors,
                                    work, admits, poison=pois,
                                    screen_on=screen)
        if pois is not None and fields is not None:
            fields["screen_on"] = float(screen) if screen is not None \
                else None
            fields["n_poisoned"] = int((np.asarray(pois) > 0).sum())
        if self._replay_digests:
            expect = self._replay_digests.pop(int(round_idx), None)
            if expect is not None and expect != digest:
                raise PlanDigestError(
                    f"round {round_idx}: deterministic-restart replay "
                    f"computed install digest {digest[:12]}… but the "
                    f"write-ahead journal recorded {expect[:12]}… — "
                    "the resumed control stream diverged from what "
                    "the crashed run durably committed (differing "
                    "config/seed, or a non-deterministic decision "
                    "leaked into the plan)")
        if self.plan_transport is not None and fields is None:
            # a transport run journals the write-ahead stream for
            # EVERY round (a default scheduler plans nothing, but the
            # admit merges and fault operands are still the control
            # decision a takeover must be able to verify)
            ids = np.asarray(client_ids).reshape(-1)
            fields = {"round": int(round_idx),
                      "sampler": self.cfg.sampler,
                      "n_sampled": int(len(ids) if survivors is None
                                       else (np.asarray(survivors)
                                             > 0).sum())}
        if fields is not None and self.telemetry is not None:
            if digest is not None:
                fields["digest"] = digest
            self.telemetry.journal_event("schedule", **fields)
            if self.plan_transport is not None:
                self._wa_dirty = True
        if self.plan_transport is not None and digest is not None:
            self.plan_transport.verify(round_idx, digest,
                                       scope="install")

    def _flush_write_ahead(self) -> None:
        """Barrier the journal's writer queue so every sealed plan is
        DURABLE before the dispatch that executes it (the write-ahead
        contract; a no-op for the default synchronous journal, whose
        events are durable as soon as they return, and for
        transport-free runs)."""
        if self._wa_dirty:
            self._wa_dirty = False
            if self.telemetry is not None:
                self.telemetry.journal_flush()

    def drain_persistence(self) -> None:
        """Block until every queued off-critical-path checkpoint write
        (--pipeline's AsyncCheckpointWriter) is durable; a no-op
        otherwise. Drivers call this before any SYNCHRONOUS save (the
        manifest must rotate in order) and in their finally blocks, so
        an InjectedFault drill flushes exactly like a clean
        shutdown. Also drains the tiered state store's spill queue
        (state_tier=host) so every evicted row is durable in the host
        tail."""
        if self.ckpt_writer is not None:
            self.ckpt_writer.drain()
        if self.state_store is not None:
            self.state_store.flush()

    def close_persistence(self) -> None:
        """drain_persistence + stop the writer threads (driver
        shutdown). Idempotent."""
        if self.ckpt_writer is not None:
            self.ckpt_writer.close()
        if self.state_store is not None:
            self.state_store.close()

    def _scheduler_active(self) -> bool:
        """True when an attached scheduler can actually produce plans
        (non-default policy) — the scanned path must then run the
        fault-composition pass even with dropout/stragglers off."""
        return self.scheduler is not None and not self.scheduler.is_default

    def _journal_fault(self, kind: str, round_idx: int) -> None:
        """Record an InjectedFault about to raise (utils/faults) in the
        run journal — the crash boundary is then visible in the run's
        own record, not just the process exit status."""
        if self.telemetry is not None:
            self.telemetry.journal_event("injected_fault", fault=kind,
                                         round=int(round_idx))
            self.telemetry.flush()

    def set_fault_schedule(self,
                           schedule: Optional[FaultSchedule]) -> None:
        """Install (or clear, with None) a deterministic fault script:
        scripted client drops override/augment the random
        client_dropout draw, scripted slow fractions compose (min)
        with the random straggler draw, crash_after raises
        InjectedFault once that round has fully completed, and
        crash_in_span kills the span CONTAINING that round before any
        of it commits — the two preemption points a checkpoint/resume
        test (or chaos drill) recovers from. Note crash_in_span
        RE-FIRES if the schedule is still installed after resume
        (resume restarts the uncommitted round — see FaultSchedule);
        clear it with set_fault_schedule(None) for a drill that should
        progress past the crash."""
        self.fault_schedule = schedule

    def trace_round_programs(self, batch,
                             include_span: bool = False,
                             span_len: int = 2) -> dict:
        """{variant: ClosedJaxpr} of the three single-round programs
        THIS model dispatches — the graftaudit (analysis/audit) hook
        for auditing a real workload rather than the CLI's synthetic
        one. `batch` is a (client_ids, data, mask) triple exactly as
        `model(batch)` takes it; only its shapes/dtypes matter (the
        trace is abstract — nothing executes, no state moves). The
        traced body is `round.make_train_fn`'s COHORT round_step — the
        program the per-round jit compiles, operating on the gathered
        [num_workers, D] CohortState rows (jax.eval_shape over the
        gather body supplies their avals; nothing executes) — so what
        the auditor walks is what `model(batch)` dispatches, and a
        population-shaped operand showing up in it is exactly the
        AU004 regression the audit hard-errors on.

        include_span=True adds a "span" entry: the scanned
        `train_rounds` program over `span_len` stacked copies of the
        batch (round.stack_batch_for_span) — what the mesh tier
        (graftmesh) prices per-link, here traceable over the real
        workload/mesh too."""
        from commefficient_tpu.federated.round import (
            audit_batch_variants, stack_batch_for_span,
        )
        client_ids, data, mask = batch
        rb = fround.RoundBatch(
            jnp.asarray(np.asarray(client_ids, np.int32)),
            tuple(jnp.asarray(d) for d in data),
            jnp.asarray(np.asarray(mask, np.float32)))
        # the lr operand must have the DISPATCHED aval: with a
        # per-parameter scale vector _lr() ships a [D] f32 array, and
        # auditing a scalar-lr program instead would walk a program
        # this model never runs
        lr = (jnp.asarray(0.1 * self.lr_scale_vec)
              if self.lr_scale_vec is not None else jnp.float32(0.1))
        cohort = jax.eval_shape(self._train_round.gather_fn,
                                self.clients, rb.client_ids)
        out = {}
        for variant, vb in audit_batch_variants(rb, self.cfg).items():
            out[variant] = jax.make_jaxpr(self._train_round.round_step)(
                self.server, cohort, vb, lr, self._key)
        if include_span:
            span = stack_batch_for_span(rb, span_len)
            # stacking handles both lr avals: [span_len] for the
            # scalar, [span_len, D] for a per-parameter scale vector
            lrs = jnp.stack([lr] * span_len)
            out["span"] = jax.make_jaxpr(
                self._train_round.train_rounds)(
                self.server, self.clients, span, lrs, self._key)
        return out

    def client_rows_payload(self, clients=None,
                            tier: Optional[dict] = None
                            ) -> Optional[dict]:
        """The O(cohort) client-state checkpoint payload
        (utils/checkpoint `crows_*` keys): the touched-row id set, the
        gathered rows of every tracked state block for exactly those
        ids, and (topk_down) the init-weights base vector untouched
        rows are reconstructed from. None when this model cannot
        guarantee row sparseness — stateless configs (nothing to
        save), or a resume from a legacy dense checkpoint (unknown
        touched set) — in which case callers fall back to the dense
        `clients` save path.

        The device gather pads the id list to a 256 multiple so its
        program recompiles O(log) times over a run, not per save; the
        host transfer is explicit (mh.gather_host), so span-boundary
        saves stay transfer-guard-clean.

        `clients`: optional ClientState override — the pipelined span
        checkpoint (training/scanloop snapshot) persists span t's
        state while self.clients already points at span t+1's
        in-flight result. `tier`: the matching snapshot_tier() dict
        under state_tier=host (the LRU/touched bookkeeping at that
        same boundary).

        Under the tiered store (state_tier=host) the payload comes
        from the store instead: resident rows via an O(working set)
        padded-256 SLOT gather, evicted rows straight from the host
        tail with no device work at all (the satellite fix — a cold
        million-client tail costs the save zero gather bytes), plus
        the LRU order/slot map so resume replays the exact eviction
        stream."""
        if clients is None:
            clients = self.clients
        if self.state_store is not None:
            return self.state_store.checkpoint_rows(clients, tier=tier)
        tracked = [l.ndim == 2 for l in clients]
        if not any(tracked):
            return None
        if not self._sparse_rows_ok:
            return None
        ids = (np.sort(np.fromiter(self._touched, np.int64))
               if self._touched else np.zeros((0,), np.int64))
        payload = {"ids": ids}
        if self._init_weights_host is not None:
            payload["base_weights"] = self._init_weights_host
        empty = np.zeros((0,), np.float32)
        if len(ids) == 0:
            for name in ("errors", "velocities", "weights"):
                payload[name] = empty
            return payload
        padded = np.pad(ids, (0, (-len(ids)) % 256), mode="edge")
        gidx = mh.globalize(self.mesh, self._P(),
                            padded.astype(np.int32))
        for name, used in zip(("errors", "velocities", "weights"),
                              tracked):
            if not used:
                payload[name] = empty
                continue
            field = getattr(clients, name)
            payload[name] = np.asarray(
                mh.gather_host(field[gidx]))[:len(ids)]
        return payload

    @property
    def checkpoint_fingerprint(self) -> dict:
        """The config-compatibility fingerprint checkpoints written by
        this model embed, and resumes into it must match."""
        from commefficient_tpu.utils.checkpoint import config_fingerprint
        return config_fingerprint(self.cfg, self.num_clients)

    def _survivors_for_round(self, round_idx: int, client_ids
                             ) -> Optional[np.ndarray]:
        """[W] f32 survivor mask for one round, or None when nothing
        drops clients (the mask-free fast path — None keeps the jitted
        round on the exact program a dropout-free build traces).
        Deterministic in (cfg.seed, round_idx), so crash->resume
        replays the identical masks. Host-side by design: the mask
        enters the jitted round as data AND drives byte accounting
        without any device sync."""
        ids = np.asarray(client_ids)
        mask = None
        if self.cfg.client_dropout > 0:
            mask = bernoulli_survivors(self.cfg.seed, round_idx,
                                       ids.shape[0],
                                       self.cfg.client_dropout)
        if self.fault_schedule is not None:
            scripted = self.fault_schedule.survival_mask(round_idx, ids)
            if scripted is not None:
                mask = scripted if mask is None else mask * scripted
        return mask

    def _work_for_round(self, round_idx: int, client_ids
                        ) -> Optional[np.ndarray]:
        """[W] f32 work fractions for one round, or None when nothing
        slows clients down. Deterministic in (cfg.seed, round_idx),
        like the survivor draw; scripted FaultSchedule.slow fractions
        compose with the random draw by elementwise minimum (the
        slower cause wins)."""
        W = np.asarray(client_ids).shape[0]
        work = None
        if self.cfg.straggler_rate > 0:
            work = straggler_work_fractions(
                self.cfg.seed, round_idx, W, self.cfg.straggler_rate,
                self.cfg.straggler_min_work)
        if self.fault_schedule is not None:
            scripted = self.fault_schedule.work_fractions(round_idx, W)
            if scripted is not None:
                work = (scripted if work is None
                        else np.minimum(work, scripted))
        return work

    def _faults_for_round(self, round_idx: int, client_ids
                          ) -> Tuple[Optional[np.ndarray],
                                     Optional[np.ndarray]]:
        """(survivors, work) for one round, with the straggler cutoff
        applied: a work fraction below Config.straggler_cutoff
        DEGRADES to the dropout path — its survivor bit is zeroed (no
        upload, state rows bit-untouched, accounting charges nothing)
        and its work entry is reset to the inert 1.0. A work vector
        that ends up all-ones collapses back to None, so such a round
        runs the EXACT dropout program an explicitly-dropped client
        traces — the bit-identity the cutoff contract promises. When
        work survives, a missing survivor mask is filled with ones:
        the work program always carries both operands (round.py traces
        exactly three programs).

        A scheduler RoundPlan composes through the SAME operands
        before the cutoff pass: idle over-provisioned slots zero the
        survivor mask (bit-exactly the dropped-client path) and
        deadline fractions min-compose with the straggler draw — the
        slower cause wins, and a deadline fraction below the straggler
        cutoff degrades to dropout like any other. The consumed plan
        is journaled as a `schedule` event, so scheduling decisions
        are in the run's own record."""
        surv = self._survivors_for_round(round_idx, client_ids)
        work = self._work_for_round(round_idx, client_ids)
        plan = (self.scheduler.take_plan(round_idx)
                if self.scheduler is not None else None)
        if plan is not None:
            if plan.active is not None:
                surv = (plan.active if surv is None
                        else surv * plan.active)
                self._plan_active[int(round_idx)] = plan.active
            if plan.work is not None:
                w = np.asarray(plan.work, np.float32)
                work = w if work is None else np.minimum(work, w)
            if plan.screen_mult is not None:
                # adaptive screening (ISSUE 17): a replayed/broadcast
                # plan's stamped multiplier wins over the local
                # controller's value (_screen_flag pops this)
                self._plan_screen_mult[int(round_idx)] = float(
                    plan.screen_mult)
            if plan.controls:
                # controller bank (ISSUE 20): the plan-carried values
                # are the authoritative trajectory — stash them for
                # compose-time application (staleness decay) and
                # install them as the bank's live state, so followers,
                # replayed rounds, and takeover coordinators all run
                # the journaled decision instead of recomputing one
                self._plan_controls[int(round_idx)] = dict(
                    plan.controls)
                if self.control_bank is not None:
                    self.control_bank.install(plan.controls)
            # journaling is deferred to _seal_plan (ISSUE 12): the
            # `schedule` event must carry the digest of the FULLY
            # composed decision (async admits land after this pass)
            # and be durable before dispatch — write-ahead
            fields = plan.journal_fields()
            if self.plan_transport is not None:
                # transport runs journal the FULL serialized plan: the
                # journal is then the authoritative decision log a
                # deterministic restart REPLAYS (scheduler.
                # load_replay_plans installs these bytes for replayed
                # rounds instead of recomputing decisions against a
                # wall-clock-fed tracker the replay cannot reproduce)
                from commefficient_tpu.parallel.plantransport import (
                    serialize_plan,
                )
                fields["plan"] = serialize_plan(plan).decode()
            self._plan_journal[int(round_idx)] = fields
        if work is not None:
            work = np.asarray(work, np.float32)
            cutoff = self.cfg.straggler_cutoff
            if cutoff > 0:
                below = work < cutoff
                if below.any():
                    s = (np.ones(work.shape[0], np.float32)
                         if surv is None else surv.copy())
                    s[below] = 0.0
                    surv = s
                    work = np.where(below, np.float32(1.0), work)
            if np.all(work >= 1.0):
                work = None
        if work is not None and surv is None:
            surv = np.ones(work.shape[0], np.float32)
        return surv, work

    # -- value-fault screening (ISSUE 16) --------------------------------
    def _screened_dispatch(self, round_idx: int) -> bool:
        """Whether dispatches at `round_idx` take the SCREENED program
        family (round.SCREENED_PROGRAM_VARIANTS): screening or poison
        configured statically, a scripted poison schedule installed,
        or the round inside a post-rollback forced-screen window. A
        default config outside any window builds the poison-free
        treedef, so its three programs stay byte-identical."""
        return (fround.screened_family(self.cfg)
                or round_idx < self._screen_force_until
                or (self.fault_schedule is not None
                    and bool(self.fault_schedule.poison
                             or self.fault_schedule.byzantine)))

    def _poison_values(self, round_idx: int,
                       num_slots: int) -> np.ndarray:
        """[W] f32 {0,1} composed poison mask for one round: the
        random Config.poison_rate draw (utils/faults.poison_mask, its
        own PRNG domain — deterministic in (seed, round), so a resumed
        run replays the identical faults) max-composed with any
        scripted FaultSchedule.poison slots. All-zeros when nothing
        poisons — the inert operand a screening-only round ships.

        Byzantine adversaries (ISSUE 17) ride the SAME operand: under
        Config.byzantine_rate > 0 (validate() makes the two rates
        mutually exclusive, and the attack transform keys statically
        off the rate) the flags mark adversary-controlled slots
        instead — drawn on the "byzantine" PRNG domain, max-composed
        with scripted FaultSchedule.byzantine slots."""
        if self.cfg.byzantine_rate > 0:
            mask = byzantine_mask(self.cfg.seed, round_idx, num_slots,
                                  self.cfg.byzantine_rate)
            if self.fault_schedule is not None:
                scripted = self.fault_schedule.byzantine_mask_for(
                    round_idx, num_slots)
                if scripted is not None:
                    mask = np.maximum(mask, scripted)
            return mask
        mask = poison_mask(self.cfg.seed, round_idx, num_slots,
                           self.cfg.poison_rate)
        if self.fault_schedule is not None:
            scripted = self.fault_schedule.poison_mask_for(round_idx,
                                                           num_slots)
            if scripted is not None:
                mask = np.maximum(mask, scripted)
        return mask

    def _screen_flag(self, round_idx: int) -> np.float32:
        """The traced screen-enable scalar for one round: nonzero when
        the admission screen applies (configured on, or the round is
        in a forced post-rollback window), else 0.0 — poison then
        flows through to the server state (the trip path).

        Adaptive screening (ISSUE 17): under Config.adaptive_screen
        the scalar's VALUE is the live norm multiplier — the traced
        program never changes, the threshold is data. screen_mult_min
        > 1 keeps every on-value disjoint from the off sentinel 0. A
        consumed plan's stamped multiplier (broadcast or journal
        replay — _faults_for_round stashed it) wins over the local
        controller's, so takeover and restart REPLAY the trajectory
        instead of recomputing it."""
        on = (self.cfg.update_screen != "off"
              or round_idx < self._screen_force_until)
        if not on:
            self._plan_screen_mult.pop(int(round_idx), None)
            return np.float32(0.0)
        if self.cfg.adaptive_screen:
            mult = self._plan_screen_mult.pop(int(round_idx), None)
            if mult is None and self.screen_ctl is not None:
                mult = self.screen_ctl.plan_mult()
            if mult is not None:
                return np.float32(mult)
        return np.float32(1.0)

    def force_screen_rounds(self, n: int) -> None:
        """Force the in-round admission screen ON for the next `n`
        dispatched rounds — the finite-frontier rollback's quarantine
        window (Config.rollback_screen_rounds): after walking back to
        a finite checkpoint, the replayed rounds re-draw the identical
        poison (pure in (seed, round)) but the forced screen admits it
        out, so the run crosses the trip boundary finitely."""
        self._screen_force_until = max(
            self._screen_force_until, self._rounds_done + int(n))

    # -- robust aggregation + adaptive screening (ISSUE 17) ---------------
    def _journal_aggregator(self, round_idx: int,
                            stats: np.ndarray) -> None:
        """Journal one round's `aggregator` event from the device
        agg_stats vector (round.RoundMetrics.agg_stats): mean clients
        trimmed per cell, clients norm-clipped, the l2 residual
        between the robust aggregate and the admitted mean, and the
        contributing-client count. A non-finite residual (an entirely
        corrupt cohort) journals as -1.0 — the journal is strict
        JSON."""
        resid = float(stats[2])
        self.telemetry.journal_event(
            "aggregator", round=int(round_idx),
            aggregator=self.cfg.aggregator,
            n_trimmed=round(float(stats[0]), 6),
            n_clipped=int(stats[1]),
            residual_l2=(round(resid, 6) if np.isfinite(resid)
                         else -1.0),
            n_contrib=int(stats[3]))

    # -- compressor plugins + differential privacy (ISSUE 19) -------------
    def _journal_compressor(self, round_idx: int,
                            up_bytes: float) -> None:
        """Journal one committed round's `compressor` event: the
        mode's static per-client wire geometry plus the round's
        accounted upload total — summarize() accumulates these into
        the per-mode bytes-on-wire table."""
        self.telemetry.journal_event(
            "compressor", round=int(round_idx), mode=self.cfg.mode,
            wire_bytes=float(self.cfg.upload_bytes),
            up_bytes=round(float(up_bytes), 3))

    def _journal_privacy(self, round_idx: int) -> None:
        """Journal one committed round's `privacy` event (cumulative
        epsilon over the rounds committed so far) and fail LOUDLY
        once the budget is exhausted. The exhausted round is
        journaled BEFORE the raise, so the journal records the
        crossing a post-mortem needs."""
        eps = float(self.privacy.epsilon(round_idx + 1))
        if self.telemetry is not None:
            self.telemetry.journal_event(
                "privacy", round=int(round_idx),
                epsilon=round(eps, 6),
                sigma=float(self.cfg.dp_noise_mult),
                clip=float(self.cfg.dp_clip),
                delta=float(self.cfg.dp_delta))
        target = float(self.cfg.dp_target_epsilon)
        if target > 0 and eps > target:
            raise RuntimeError(
                f"privacy budget exhausted at round {round_idx}: "
                f"cumulative epsilon {eps:.4f} exceeds "
                f"--dp_target_epsilon {target:g} at delta "
                f"{self.cfg.dp_delta:g}. Raise --dp_noise_mult, "
                f"raise --dp_target_epsilon, or train fewer rounds.")

    def _observe_screening(self, round_idx: int, n_screened: int,
                           survivors) -> None:
        """Feed the adaptive-screen controller one committed round's
        observed screened count — EVERY round, zero included, so the
        trajectory is a pure function of the observation stream — and
        journal a `screen_adapt` event when the threshold moved."""
        n_cohort = (int((np.asarray(survivors) > 0).sum())
                    if survivors is not None else 0)
        changed = self.screen_ctl.observe(round_idx, n_screened,
                                          n_cohort)
        if changed is not None and self.telemetry is not None:
            old, new, rate = changed
            self.telemetry.journal_event(
                "screen_adapt", round=int(round_idx),
                old_mult=round(old, 6), new_mult=round(new, 6),
                rate=round(rate, 6),
                target=float(self.cfg.target_screened_rate))

    # -- plan-riding controller bank (ISSUE 20) --------------------------
    @staticmethod
    def _control_signals(row) -> dict:
        """Commit-time signal dict for ControllerBank.observe_commit
        from one materialized [NUM_METRICS] telemetry row (or {} when
        metrics are off — controllers then skip the observation)."""
        if row is None or getattr(row, "size", 0) == 0:
            return {}
        row = np.asarray(row, np.float32)
        return {"estimate_residual": float(
            row[METRIC_INDEX["estimate_residual"]])}

    def _journal_control_events(self) -> None:
        """Drain the bank's queued adjustments into `control` journal
        events — the single journaling seam for draw-time (stamp),
        commit-time (observe_commit), and span (feed_span)
        adjustments alike."""
        if self.control_bank is None:
            return
        events = self.control_bank.take_events()
        if self.telemetry is None:
            return
        for adj in events:
            self.telemetry.journal_event(
                "control", round=int(adj.round_idx),
                controller=str(adj.controller),
                signal=round(float(adj.signal), 6),
                old=round(float(adj.old), 6),
                new=round(float(adj.new), 6),
                clamped=bool(adj.clamped))

    def _apply_plan_controls(self, round_idx: int) -> None:
        """Apply one consumed plan's stashed controller values to the
        operands the round is about to compose with — currently the
        async admission buffer's staleness decay. Runs BEFORE
        async_admit.compose so the defer/admit weights this round
        journals and digests use exactly the plan-carried discount."""
        controls = self._plan_controls.pop(int(round_idx), None)
        if (controls and self.async_admit is not None
                and "staleness_decay" in controls):
            self.async_admit.decay = float(
                np.float32(controls["staleness_decay"]))

    # -- reference API surface -------------------------------------------
    def train(self, training: bool):
        self.training = training

    def __call__(self, batch):
        if self.training:
            return self._call_train(batch)
        return self._call_val(batch)

    def finalize(self):
        """No worker processes to tear down (reference needed this at
        fed_aggregator.py:196-203); kept for API parity."""

    @property
    def ps_weights(self) -> jax.Array:
        return self.server.ps_weights

    def state_dict(self):
        """Current PS weights as the model's parameter pytree
        (reference materializes this through a __getattr__ hack,
        fed_aggregator.py:372-376)."""
        return self.unravel(self.server.ps_weights)

    def load_state(self, ckpt) -> int:
        """Install a loaded `utils.checkpoint.Checkpoint` into this
        model, globalizing every field onto this model's mesh — the
        multi-controller-safe resume path (every process loads the same
        file from shared storage, the reference's rank-0 rendezvous
        inverted). Returns the checkpoint's scheduler step.

        Validates the checkpoint's config fingerprint (when present)
        against this model — a mismatched resume raises
        CheckpointMismatchError here even if the caller skipped
        validation at load_checkpoint time."""
        if ckpt.fingerprint is not None:
            from commefficient_tpu.utils.checkpoint import (
                validate_fingerprint,
            )
            validate_fingerprint(ckpt.fingerprint,
                                 self.checkpoint_fingerprint,
                                 "<loaded checkpoint>")
        P = self._P
        s = ckpt.server
        # globalize_owned, not globalize: the scanned span DONATES the
        # server state, so the resumed buffers must be XLA-owned — a
        # zero-copied checkpoint numpy array in the donation chain is
        # the heap-corruption class multihost.zeros documents
        self.server = fround.ServerState(
            mh.globalize_owned(self.mesh, P(), s.ps_weights),
            mh.globalize_owned(self.mesh, P(), s.Vvelocity),
            mh.globalize_owned(self.mesh, P(), s.Verror),
            mh.globalize_owned(self.mesh, P(), s.round_idx))
        if ckpt.client_rows is not None:
            # O(cohort) checkpoint (crows_* keys): rebuild the sharded
            # population blocks from init — zeros, or the saved
            # init-weights tile for topk_down — then scatter the saved
            # touched rows in. Bit-exact: untouched rows never left
            # their init values (dropped clients' rows are written
            # back bit-untouched), so init + touched rows IS the full
            # state.
            rows = ckpt.client_rows
            if rows.get("base_weights") is not None:
                self._init_weights_host = np.asarray(
                    rows["base_weights"], np.float32)
            base = (self._init_weights_host
                    if self._init_weights_host is not None
                    else np.asarray(ckpt.server.ps_weights, np.float32))
            if self.state_store is not None:
                # tiered store (ISSUE 11): fresh working-set block at
                # init values, then the store rebuilds the tiers —
                # rows recorded resident (crows_lru_*) scatter back
                # into their slots so the eviction stream replays;
                # everything else (incl. a payload written by a
                # state_tier=device run, which has no lru keys) lands
                # in the host tail. Bit-exact either way: residency
                # never changes row values.
                self.state_store.set_init_weights(
                    self._init_weights_host)
                self.clients = fround.init_client_state(
                    self.cfg,
                    fround.client_state_rows(self.cfg,
                                             self.num_clients),
                    jnp.asarray(base), mesh=self.mesh)
                self.clients = self.state_store.load_rows(
                    self.clients, rows)
                # the store's LRU + tail are the touched set for a
                # tiered model; the host _touched mirror stays unused
                self._sparse_rows_ok = True
                self._finish_load(ckpt)
                return ckpt.scheduler_step
            self.clients = fround.init_client_state(
                self.cfg, self.num_clients, jnp.asarray(base),
                mesh=self.mesh)
            ids = np.asarray(rows["ids"], np.int64)
            self._touched = set(int(i) for i in ids)
            self._sparse_rows_ok = True
            if len(ids):
                gidx = mh.globalize(self.mesh, P(),
                                    ids.astype(np.int32))
                new = self.clients
                for name in ("errors", "velocities", "weights"):
                    data = np.asarray(rows.get(name, ()))
                    field = getattr(new, name)
                    if data.ndim != 2 or field.ndim != 2:
                        continue
                    placed = mh.globalize(self.mesh, P(),
                                          data.astype(np.float32))
                    new = new._replace(
                        **{name: field.set_rows(gidx, placed)})
                self.clients = new
        elif ckpt.clients is not None:
            if self.state_store is not None:
                # legacy dense blocks into the tiered store: the
                # vectorized diff against init recovers the touched
                # set the dense format never recorded; touched rows
                # land in the host tail, the working set starts cold,
                # and this model's own saves stay sparse
                dense = {name: np.asarray(getattr(ckpt.clients, name))
                         for name in self.state_store.fields}
                self.state_store.import_dense(dense)
                self._sparse_rows_ok = True
            else:
                # legacy dense client blocks: place them whole. The
                # touched-row set is unrecoverable from a dense save,
                # so this model's own checkpoints fall back to the
                # dense format from here on (client_rows_payload ->
                # None) rather than silently dropping pre-resume rows
                # from sparse saves.
                specs = fround.client_state_specs(ckpt.clients)
                # globalize_owned: these blocks enter the scatter/span
                # donation chain (see the server fields above); leaf
                # by leaf, so a RowBlock's tiles land sharded as tiles
                self.clients = jax.tree.map(
                    lambda leaf, spec: mh.globalize_owned(
                        self.mesh, spec, np.asarray(leaf)),
                    ckpt.clients, specs)
                if any(f.ndim == 2 for f in ckpt.clients):
                    self._sparse_rows_ok = False
        self._finish_load(ckpt)
        return ckpt.scheduler_step

    def _finish_load(self, ckpt) -> None:
        """The state-block-independent half of load_state: accounting,
        throughput, scheduler, sampler, async-admission, and the host
        round mirrors — shared by the device-tier and tiered-store
        resume paths."""
        if ckpt.accountant_state:
            self.accountant.load_state_dict(ckpt.accountant_state)
        if ckpt.throughput:
            # per-client throughput EMA / participation — bit-exact
            # resume (telemetry/clients.py; test_telemetry proves it)
            self.throughput.load_state_dict(ckpt.throughput)
        if ckpt.scheduler and self.scheduler is not None:
            # scheduler counters (sched_* keys) — attach the run's
            # RoundScheduler BEFORE load_state so this lands
            self.scheduler.load_state_dict(ckpt.scheduler)
        if ckpt.sampler and self.data_sampler is not None:
            # FedSampler stream state (smp_* keys) — attach the run's
            # sampler (attach_round_scheduler) BEFORE load_state; the
            # drivers then consume the restored mid-epoch stream via
            # sampler.resolve_resume instead of the head-replay
            # fast-forward
            self.data_sampler.load_state_dict(ckpt.sampler)
        if ckpt.async_admit and self.async_admit is not None:
            # pending async admissions (asyb_* keys): the resumed run
            # admits exactly what the uninterrupted one would have
            self.async_admit.load_state_dict(ckpt.async_admit)
        if ckpt.prev_change_words is not None:
            self._prev_change_words = ckpt.prev_change_words
        # resync the host round mirror so dropout draws / crash points
        # continue exactly where the checkpointed run left off (the
        # stage counter too: a resumed run has no in-flight prefetch —
        # a lost one replays from the restored sampler cursor)
        self._rounds_done = int(np.asarray(ckpt.server.round_idx))
        self._rounds_staged = self._rounds_done

    # -- internals --------------------------------------------------------
    def _feed(self, rows, leading_axes: int = 0):
        """Place one round-batch leaf on the mesh: per-process local
        rows via shard_rows (the default), or — under the feed_global
        fallback — the full global value via globalize with the same
        clients-sharded spec."""
        if self.feed_global:
            P = self._P
            spec = P(*([None] * leading_axes), "clients",
                     *([None] * (np.ndim(rows) - leading_axes - 1)))
            return mh.globalize(self.mesh, spec, rows)
        return mh.shard_rows(self.mesh, rows, leading_axes=leading_axes)

    def _lr(self):
        if self._optimizer is None:
            raise RuntimeError("attach a FedOptimizer before training")
        lr = self._optimizer.param_groups[0]["lr"]
        # per-parameter LR scaling (finetune freezing / Fixup param
        # groups) applies in EVERY mode: for fedavg the [D] vector
        # reaches the client's local SGD steps (fedavg_step broadcasts
        # it elementwise), while the server update stays at lr=1.
        if self.lr_scale_vec is not None:
            return lr * self.lr_scale_vec
        return lr

    def stage_round(self, batch) -> _StagedRound:
        """The HOST half of one round dispatch (ISSUE 10 split):
        crash-in-flight check, fault/schedule composition, async
        admission, and explicit operand placement — everything
        `model(batch)` does before the device sees the round. Pure
        host work keyed by the staged round index (deterministic fault
        draws), so the pipelined driver may stage round t+1 while
        round t executes on device; rounds must be staged and
        committed in the same order. `_call_train` composes
        stage+commit back-to-back, which IS the pre-split synchronous
        path operation for operation."""
        client_ids, data, mask = batch
        this_round = self._rounds_staged
        # mid-span preemption, per-round path: each round is its own
        # span of one — the kill lands while this round's program is
        # in flight, so NOTHING commits (state, accounting, counter)
        if (self.fault_schedule is not None
                and self.fault_schedule.should_crash_in_span(
                    this_round, 1)):
            self._journal_fault("crash_in_span", this_round - 1)
            raise InjectedFault(this_round - 1)
        # graftscope (ISSUE 13): the `plan` stage — fault/schedule
        # composition, async admission, and the write-ahead seal; the
        # scheduler's broadcast/install work nests inside as
        # `plan_install` spans
        with TRACE.span("plan", round=this_round):
            survivors, work = self._faults_for_round(this_round,
                                                     client_ids)
            self._apply_plan_controls(this_round)
            admits = ()
            if self.async_admit is not None:
                # buffered async aggregation (federated/async_agg):
                # defer this round's stragglers onto the
                # dropped-client path and merge admissions due this
                # round into the cohort operands
                (client_ids, data, mask, survivors,
                 work) = self.async_admit.compose(
                    this_round, client_ids, data, mask, survivors,
                    work)
                admits = self.async_admit.last_admits
            # value-fault screening (ISSUE 16): a screened-family
            # round always ships the full operand trio — ones-filled
            # survivors, the composed poison mask, the traced screen
            # flag — so exactly two screened programs exist and the
            # per-round screen decision never retraces
            pois = screen = None
            if self._screened_dispatch(this_round):
                W = np.asarray(client_ids).shape[0]
                pois = self._poison_values(this_round, W)
                screen = self._screen_flag(this_round)
                if survivors is None:
                    survivors = np.ones(W, np.float32)
            # write-ahead plan seal (ISSUE 12): digest + journal the
            # composed control decision, flush it durable before this
            # round's dispatch, and cross-check against the other
            # controllers / the replayed journal. No-op without a
            # transport or replay stream (beyond the journaling the
            # scheduler always got).
            self._seal_plan(this_round, client_ids, survivors, work,
                            admits, pois=pois, screen=screen)
            self._flush_write_ahead()

        # tiered client state (ISSUE 11): assign device slots AFTER
        # admission composition (an admitted client needs a slot too).
        # Pure host bookkeeping — the spill/restore device ops run at
        # commit time against the then-current block, so staging may
        # still run one round ahead under Config.pipeline.
        tier_plan = None
        ids_for_device = np.asarray(client_ids, np.int32)
        if self.state_store is not None:
            tier_plan = self.state_store.plan_round(client_ids)
            ids_for_device = tier_plan.slots

        with TRACE.span("stage", round=this_round):
            P = self._P
            lr = self._lr()
            # explicit placement for BOTH lr shapes: a raw python
            # float operand is an IMPLICIT host->device transfer at
            # every dispatch — the first thing --debug_transfer_guard
            # caught. np.float32(lr) is the identical f32 value the
            # weak-typed scalar would have become, so results are
            # bit-unchanged.
            lr = mh.globalize(self.mesh, P(),
                              lr if isinstance(lr, np.ndarray)
                              else np.float32(lr))
            placed = fround.RoundBatch(
                mh.globalize(self.mesh, P(), ids_for_device),
                tuple(self._feed(d) for d in data),
                self._feed(mask),
                None if survivors is None
                else mh.globalize(self.mesh, P(), survivors),
                None if work is None
                else mh.globalize(self.mesh, P(), work),
                None if pois is None
                else mh.globalize(self.mesh, P(), pois),
                None if pois is None
                else mh.globalize(self.mesh, P(), screen))
        self._rounds_staged = this_round + 1
        return _StagedRound(this_round, placed, lr,
                            np.asarray(client_ids), survivors,
                            tier_plan)

    def commit_staged(self, staged: _StagedRound):
        """The DISPATCH half: the gather->round->scatter bracket plus
        the lagged accounting/telemetry bookkeeping. Donation contract
        (Config.donate_round_state): the round jit donates the
        gathered CohortState and the scatter-back jit donates the full
        ClientState — self.clients is reassigned from the result below
        and never read in between. ServerState is deliberately NOT
        donated on this path: the prev_weights reference captured here
        is read AFTER dispatch for the one-round-lagged accounting
        bitset, and a donated ps_weights would be a deleted buffer by
        then (round.ROUND_DEAD_ARGNUMS / SCATTER_DEAD_ARGNUMS are the
        authoritative declarations). Except above
        config.IN_PLACE_MIN_D parameters (Config.server_in_place):
        there the round program takes the ServerState donated too and
        hands the packed bitset back itself."""
        prev_weights = (None if self.cfg.server_in_place
                        else self.server.ps_weights)
        this_round = staged.round_idx
        if staged.tier_plan is not None:
            # tier motion first (ISSUE 11): spill-gather the plan's
            # eviction victims from the CURRENT block (their values
            # include every earlier round's scatter-back), then
            # restore-scatter the misses' host rows into their slots —
            # both through the round handle's existing state-motion
            # programs, so the gather below reads a fully-resident
            # working set. The graftscope bracket carries the round
            # tag the nested tier_spill/tier_restore spans inherit.
            with TRACE.span("tier_motion", round=this_round):
                self.clients = self.state_store.execute(
                    self.clients, staged.tier_plan)
        with TRACE.span("dispatch", round=this_round):
            self.server, self.clients, metrics = self._train_round(
                self.server, self.clients, staged.batch, staged.lr,
                self._key)
        self._rounds_done = this_round + 1
        # O(cohort) checkpoint support: these rows may now differ from
        # their init values (dropped clients' rows were written back
        # bit-untouched, but over-including them only costs a few
        # zero rows in the sparse save). The tiered store tracks its
        # own touched set (LRU + tail) — this host mirror would be
        # write-only dead weight there.
        if self.state_store is None:
            self._touched.update(
                int(i) for i in staged.client_ids.reshape(-1))

        # Communication accounting with ONE round of lag: this round's
        # change bitset is dispatched and its device->host copy started
        # asynchronously; the popcount consumes the PREVIOUS round's
        # bits, which are already on the host. Materializing the fresh
        # bits here instead would block on the round that was just
        # dispatched — a device sync per round.
        with TRACE.span("collect", round=this_round):
            if metrics.change_bits is not None:
                # Config.server_in_place: the round program donated
                # the old weights and packed the bits itself
                bits = metrics.change_bits
            else:
                bits = self._pack_bits(self.server.ps_weights
                                       - prev_weights)
            bits.copy_to_host_async()
            # screened family (ISSUE 16): accounting charges the
            # EFFECTIVE mask — host survivors x device admission — so
            # a screened client is billed exactly like a dropped one.
            # The device_get is a sync, but only screened configs ever
            # take it; the default path reads the host copy as before.
            # Robust aggregation (ISSUE 17) narrows the billed mask
            # once more: a client the order statistics kept NO cell of
            # (metrics.contributors) shipped an update the aggregate
            # provably contains nothing of, so it is billed like a
            # screened one.
            surv_acc = staged.survivors
            if metrics.admitted is not None:
                surv_acc = np.asarray(jax.device_get(metrics.admitted),
                                      np.float32)
            surv_bill = surv_acc
            if metrics.contributors is not None:
                surv_bill = np.asarray(
                    jax.device_get(metrics.contributors), np.float32)
            prev_words = self._prev_change_words
            if prev_words is not None:
                # the lagged read: ready when asked for unless the
                # device is a round behind, and then the host waits
                # here
                with TRACE.span("device_wait"):
                    prev_words = np.asarray(prev_words)
            download, upload = self.accountant.record_round(
                staged.client_ids, prev_words, survivors=surv_bill)
        self._prev_change_words = bits
        n_screened = None
        if metrics.admitted is not None and staged.survivors is not None:
            n_screened = int((staged.survivors > 0).sum()
                             - (surv_acc > 0).sum())
            if n_screened > 0 and self.telemetry is not None:
                self.telemetry.journal_event(
                    "screened", round=this_round,
                    n_screened=n_screened,
                    kind=(self.cfg.update_screen
                          if self.cfg.update_screen != "off"
                          else "finite"))
        if metrics.agg_stats is not None and self.telemetry is not None:
            self._journal_aggregator(
                this_round, np.asarray(
                    jax.device_get(metrics.agg_stats), np.float64))
        if self.screen_ctl is not None and n_screened is not None:
            self._observe_screening(this_round, n_screened,
                                    staged.survivors)
        # controller bank (ISSUE 20): commit-time observation on the
        # round's device-deterministic metric row (a replayed round
        # re-observes identically), then drain every queued
        # adjustment — draw-time stamps included — into `control`
        # journal events. The device_get is a sync, but only
        # bank-enabled configs ever take it.
        if self.control_bank is not None:
            self.control_bank.observe_commit(
                this_round, self._control_signals(
                    jax.device_get(metrics.telemetry)
                    if self.cfg.telemetry else None))
            self._journal_control_events()
        # compressor + privacy journaling (ISSUE 19): per committed
        # round, after accounting so up_bytes is this round's billed
        # total. _journal_privacy raises once the epsilon budget is
        # exhausted — the round above fully committed, so the abort
        # lands at the same clean boundary an injected crash does.
        if self.telemetry is not None:
            self._journal_compressor(this_round, upload.sum())
        if self.privacy is not None:
            self._journal_privacy(this_round)

        # telemetry, one-round lag (same discipline as the metric
        # return below): hand the session this round's DEVICE metric
        # vector + example counts; it materializes the previous round's
        # (already complete — free) and journals it
        sched_mask = self._plan_active.pop(this_round, None)
        if self.telemetry is not None:
            self.telemetry.on_round(
                this_round, staged.client_ids,
                metrics.telemetry if self.cfg.telemetry else None,
                metrics.num_examples,
                comm=(float(download.sum()), float(upload.sum())),
                scheduled=sched_mask)
            if self.state_store is not None:
                # tier residency telemetry (ISSUE 11): working-set
                # hit/miss and spill/restore deltas for this round —
                # journal-schema-checked by validate_journal, hit rate
                # surfaced by summarize()
                self.telemetry.journal_event(
                    "state_tier", round=this_round,
                    **self.state_store.take_journal_fields())
                # checksummed tiers (ISSUE 16): any tail rows that
                # failed verification since the last drain journal
                # one loud `state_quarantine` event each
                for q in self.state_store.take_quarantine_events():
                    self.telemetry.journal_event(
                        "state_quarantine", round=this_round, **q)

        # injected preemption: the round above fully completed (state,
        # accounting, round counter) — crash at the exact boundary a
        # real preemption would leave behind
        if (self.fault_schedule is not None
                and self.fault_schedule.should_crash(this_round)):
            self._journal_fault("crash_after", this_round)
            raise InjectedFault(this_round)

        # metrics stay device arrays: callers that float() them decide
        # when to pay the sync (drivers materialize with a 1-round lag)
        return [metrics.losses, *metrics.metrics, download, upload]

    def _call_train(self, batch):
        """batch = (client_ids, data, mask). `client_ids` is always the
        GLOBAL [W] participant list (cheap; the sampler runs identically
        on every process). In a multi-controller run, `data`/`mask`
        carry ONLY this process's rows (FedLoader feed_slice →
        multihost.local_row_slice): per-process batch feeding — no host
        materializes the global batch."""
        # graftscope: the parent of this round's plan, stage,
        # tier_motion, dispatch and collect spans, which inherit its id
        with TRACE.span("round", round=self._rounds_staged):
            return self.commit_staged(self.stage_round(batch))

    def run_rounds(self, client_ids, data, mask, lrs, account: bool = True):
        """Run N federated rounds as ONE device program (scanned; see
        round.train_rounds). client_ids: [N, W]; data: pytree of
        [N, W, B, ...]; mask: [N, W, B]; lrs: [N].

        Composed from `dispatch_rounds` (host staging + the async
        device dispatch) and `collect_rounds` (blocking on the span's
        results, then accounting/telemetry/crash bookkeeping) — the
        ISSUE 10 split the pipelined staging loop uses to overlap span
        t+1's dispatch with span t's collection. Called through here
        the two halves run back-to-back: the pre-split synchronous
        behavior, operation for operation.

        Returns (losses [N, W], metrics [N, W]..., download, upload)
        with download/upload the span's total BYTES (scalars — the
        accountant's per-round rows are cohort-indexed since ISSUE 9,
        so there is no population-length vector to hand back, and
        every caller only ever consumed the totals). account=False
        returns zeros and skips the per-round popcount work, but the
        [N, D/32] bitset transfer and staleness bookkeeping still
        happen so later accounted rounds stay correct.

        Fault tolerance: per-round survivor masks (client_dropout /
        FaultSchedule drops) and work fractions (straggler_rate /
        FaultSchedule slow) ride into the scanned program as [N, W]
        operands; a FaultSchedule crash_after that lands INSIDE the
        span truncates it — only the rounds up to and including the
        crash round run (and are accounted), then InjectedFault is
        raised at the identical boundary the unscanned path crashes
        at, so scanned and per-round runs checkpoint/resume
        bit-identically. A crash_in_span landing anywhere in the span
        instead kills it BEFORE any round commits (the host died while
        the span's device program was in flight) — resume must come
        from the last span boundary's checkpoint."""
        return self.collect_rounds(
            self.dispatch_rounds(client_ids, data, mask, lrs,
                                 account=account))

    def dispatch_rounds(self, client_ids, data, mask, lrs,
                        account: bool = True) -> "_SpanHandle":
        """Stage and DISPATCH one scanned span without blocking on its
        results: fault/schedule composition and async admission per
        round, explicit operand placement, the retry-guarded span
        dispatch, and the state reassignment (the returned arrays are
        futures — dispatch is asynchronous). Returns the handle
        `collect_rounds` consumes; handles must be collected in
        dispatch order. The pipelined staging loop dispatches span t+1
        before collecting span t, so the device never idles on host
        staging or persistence."""
        lrs = np.asarray(lrs, np.float32)
        ids_host = np.asarray(client_ids)
        n_rounds = ids_host.shape[0]
        first = self._rounds_done

        # mid-span preemption: the whole span is lost — no state, no
        # accounting, no counter movement; InjectedFault carries the
        # last round that actually completed (the last span boundary)
        if (self.fault_schedule is not None
                and self.fault_schedule.should_crash_in_span(
                    first, n_rounds)):
            self._journal_fault("crash_in_span", first - 1)
            raise InjectedFault(first - 1)

        # span truncation at an injected crash boundary
        crash_at = None
        if (self.fault_schedule is not None
                and self.fault_schedule.crash_after is not None
                and first <= self.fault_schedule.crash_after
                < first + n_rounds):
            crash_at = int(self.fault_schedule.crash_after)
            n_rounds = crash_at - first + 1
            ids_host = ids_host[:n_rounds]
            lrs = lrs[:n_rounds]
            data = tuple(np.asarray(d)[:n_rounds] for d in data)
            mask = np.asarray(mask)[:n_rounds]

        # per-round survivor masks + work fractions (None when nothing
        # can drop/slow — the operand-free treedefs keep the scanned
        # program a fault-free build traces). Any round with work
        # forces the full [N, W] pair: one scanned program per span.
        # With async admission on, every round runs the composition
        # pass (pending entries from earlier rounds/spans may admit
        # here) and the composed ids/data/mask rows replace the staged
        # ones — still a pure host-side merge on the cohort operands.
        surv_all = work_all = None
        pois_all = screen_all = None
        screened = self._screened_dispatch(first)
        span_idx = int(getattr(self, "_spans_dispatched", 0))
        if (self.cfg.client_dropout > 0 or self.cfg.straggler_rate > 0
                or self.fault_schedule is not None
                or self._scheduler_active()
                or self.async_admit is not None
                or self.plan_transport is not None
                or self._replay_digests
                or screened):
            # graftscope: the whole span's per-round composition is
            # ONE `plan` stage span (tagged with the first round)
            with TRACE.span("plan", round=first, span=span_idx):
                copied = False
                rows = []
                for n in range(n_rounds):
                    s, w = self._faults_for_round(first + n,
                                                  ids_host[n])
                    self._apply_plan_controls(first + n)
                    admits = ()
                    if self.async_admit is not None:
                        row_ids = ids_host[n]
                        row_data = tuple(np.asarray(d)[n]
                                         for d in data)
                        row_mask = np.asarray(mask)[n]
                        ids_n, data_n, mask_n, s, w = \
                            self.async_admit.compose(
                                first + n, row_ids, row_data,
                                row_mask, s, w)
                        admits = self.async_admit.last_admits
                        if ids_n is not row_ids:
                            # an admission rewrote this round's cohort
                            # rows — copy the span containers LAZILY
                            # (the caller's staged arrays stay
                            # untouched; the common nothing-due case
                            # pays no memcpy)
                            if not copied:
                                ids_host = np.array(ids_host,
                                                    copy=True)
                                data = tuple(
                                    np.array(np.asarray(d), copy=True)
                                    for d in data)
                                mask = np.array(np.asarray(mask),
                                                copy=True)
                                copied = True
                            ids_host[n] = ids_n
                            for d, d_n in zip(data, data_n):
                                d[n] = d_n
                            mask[n] = mask_n
                    # screened family (ISSUE 16): per-round poison
                    # mask + screen flag ride the scanned program as
                    # [N, W]/[N] operands; a forced-screen window
                    # ending mid-span just flips the DATA flag — one
                    # scanned program either way
                    pois_n = screen_n = None
                    if screened:
                        W_n = np.asarray(ids_host[n]).shape[0]
                        pois_n = self._poison_values(first + n, W_n)
                        screen_n = self._screen_flag(first + n)
                        if s is None:
                            s = np.ones(W_n, np.float32)
                    # write-ahead seal per round (ISSUE 12): the whole
                    # span's sealed records flush as one barrier
                    # below, still BEFORE the span's dispatch
                    self._seal_plan(first + n, ids_host[n], s, w,
                                    admits, pois=pois_n,
                                    screen=screen_n)
                    rows.append((s, w, pois_n, screen_n))
                ones = np.ones(ids_host.shape[1], np.float32)
                if any(w is not None for _, w, _, _ in rows):
                    work_all = np.stack(
                        [w if w is not None else ones
                         for _, w, _, _ in rows])
                    surv_all = np.stack(
                        [s if s is not None else ones
                         for s, _, _, _ in rows])
                elif any(s is not None for s, _, _, _ in rows):
                    surv_all = np.stack(
                        [s if s is not None else ones
                         for s, _, _, _ in rows])
                if screened:
                    pois_all = np.stack([p for _, _, p, _ in rows])
                    screen_all = np.asarray(
                        [f for _, _, _, f in rows], np.float32)
                    if surv_all is None:
                        surv_all = np.stack([ones] * n_rounds)

        # tiered client state (ISSUE 11): the span executes as ONE
        # device program with the working-set block on the scan carry,
        # so every miss is restored (and every victim spilled) up
        # front, each round's plan pinning the span's later cohorts
        # resident (plan_span raises an actionable error when the
        # working set cannot hold a span's distinct clients). Under
        # Config.pipeline this staging overlaps the PREVIOUS span's
        # device execution — the prefetch the tier needs to stay off
        # the critical path. The dispatched id operand becomes the
        # per-round SLOT rows; ids_host keeps the global ids for
        # accounting/telemetry.
        ids_device = ids_host
        if self.state_store is not None:
            with TRACE.span("tier_motion", round=first,
                            span=span_idx):
                plans = self.state_store.plan_span(ids_host)
                for plan in plans:
                    self.clients = self.state_store.execute(
                        self.clients, plan)
                ids_device = np.stack([p.slots for p in plans])

        if self.lr_scale_vec is not None:
            # per-parameter LR scaling — same routing _lr() applies on
            # the single-round path (incl. fedavg: the vector reaches
            # the clients' local steps)
            lrs = lrs[:, None] * self.lr_scale_vec[None, :]
        P = self._P

        # multi-controller feeding contract matches _call_train: ids
        # global, data/mask rows process-local (leading [N] span axis
        # unsharded). Dispatch is retry-guarded (utils/retry): the
        # scanned program is FUNCTIONAL — state is only assigned from
        # its result — so a transient runtime failure (coordinator
        # blip on a preemptible pod) can safely be retried without
        # half-mutated state; fatal errors re-raise immediately.
        # Donation caveat (Config.donate_round_state, default on): the
        # span jit donates BOTH state operands (run_rounds reads
        # nothing after dispatch — even the change bitset comes from
        # the span's result), so once the dispatch has CONSUMED them a
        # replay would re-dispatch deleted buffers. _span_classify
        # below closes the ISSUE 7 caveat: a transient-looking failure
        # is reclassified FATAL the moment any donated state leaf is
        # already deleted — the ORIGINAL error re-raises instead of a
        # retry that would either silently replay consumed state or
        # surface a confusing array-deleted error one attempt later.
        # Failures in the staging/globalize phase (where coordinator
        # blips actually land) leave the operands alive and retry as
        # before; --no_donate_round_state restores full span
        # retryability at the cost of transiently doubled state HBM.
        def dispatch():
            return self._train_round.train_rounds(
                self.server, self.clients,
                fround.RoundBatch(
                    mh.globalize(self.mesh, P(),
                                 np.asarray(ids_device, np.int32)),
                    tuple(self._feed(d, leading_axes=1)
                          for d in data),
                    self._feed(mask, leading_axes=1),
                    None if surv_all is None
                    else mh.globalize(self.mesh, P(), surv_all),
                    None if work_all is None
                    else mh.globalize(self.mesh, P(), work_all),
                    None if pois_all is None
                    else mh.globalize(self.mesh, P(), pois_all),
                    None if screen_all is None
                    else mh.globalize(self.mesh, P(), screen_all)),
                mh.globalize(self.mesh, P(), lrs), self._key)

        def _journal_retry(attempt: int, exc: BaseException,
                           delay: float) -> None:
            if self.telemetry is not None:
                self.telemetry.journal_event(
                    "retry", op="scanned round span",
                    attempt=int(attempt), delay_s=round(delay, 3),
                    error=repr(exc)[:200])

        def _span_classify(exc: BaseException) -> bool:
            """Transient AND safe to replay: with donation on, a
            dispatch that already consumed its state operands must not
            be re-dispatched (the ISSUE 7 retry caveat, now closed
            mechanically — tests/test_pipeline.py regression)."""
            if not is_transient_error(exc):
                return False
            if self._train_round.span_donate_argnums:
                for leaf in jax.tree.leaves((self.server, self.clients)):
                    if getattr(leaf, "is_deleted", lambda: False)():
                        return False
            return True

        # write-ahead barrier (ISSUE 12): every sealed plan of this
        # span must be durable before the span executes
        self._flush_write_ahead()
        t_dispatch0 = time.monotonic()
        # graftscope: the `dispatch` span is the HOST cost of staging
        # + dispatching the scanned program (operand placement and
        # the async dispatch call) — the device-side window is the
        # `device_execute` span collect_rounds records at the seam
        with TRACE.span("dispatch", round=first, span=span_idx):
            self.server, self.clients, metrics, bits = with_retries(
                dispatch, describe="scanned round span",
                classify=_span_classify, on_retry=_journal_retry)
        t_dispatched = time.monotonic()
        self._rounds_done = first + n_rounds
        self._rounds_staged = max(self._rounds_staged,
                                  self._rounds_done)
        if self.state_store is None:
            # tiered models track touched ids in the store (see
            # commit_staged)
            self._touched.update(
                int(i) for i in np.asarray(ids_host).reshape(-1))
        return _SpanHandle(first=first, ids_host=ids_host,
                           surv_all=surv_all, work_all=work_all,
                           crash_at=crash_at, account=account,
                           metrics=metrics, bits=bits,
                           t_dispatch0=t_dispatch0,
                           t_dispatched=t_dispatched,
                           span_idx=span_idx)

    def collect_rounds(self, handle: "_SpanHandle"):
        """Block on a dispatched span's results and COMMIT it: the
        accounting bitset device_get, per-round byte accounting, the
        span-boundary telemetry export, the injected crash_after
        boundary, and the metric gathers. Handles must be collected in
        the order their spans were dispatched (accounting and the
        change-bitset lag are sequential)."""
        first = handle.first
        ids_host = handle.ids_host
        surv_all = handle.surv_all
        metrics = handle.metrics
        account = handle.account
        crash_at = handle.crash_at

        # span byte totals (the accountant's per-round rows are
        # COHORT-indexed since ISSUE 9 — a population-length vector
        # per round was exactly the O(num_clients) host cost this
        # refactor removes; callers of this method only ever consumed
        # the totals)
        download = np.float64(0.0)
        upload = np.float64(0.0)
        # explicit device_get (not np.asarray): run_rounds is
        # transfer-guard-clean end to end — tests arm
        # analysis/runtime.forbid_transfers around the whole call
        bits_host = jax.device_get(handle.bits)
        t_blocked = time.monotonic()
        # graftscope: the device-execute window, bracketed at the
        # dispatch/collect seam — dispatch-returned to span-results-
        # forced. Under --pipeline consecutive spans' windows overlap
        # (the double buffer working); the overlap-efficiency metric
        # in summarize() takes the interval UNION. The span tag is
        # the scanned-span index --profile_spans selects on, so a
        # jax.profiler capture correlates with exactly these spans.
        TRACE.record("device_execute", handle.t_dispatched, t_blocked,
                     round=handle.first,
                     span=(handle.span_idx
                           if handle.span_idx >= 0 else None))
        with TRACE.span("collect", round=handle.first,
                        span=(handle.span_idx
                              if handle.span_idx >= 0 else None)):
            if self._prev_change_words is not None:
                # may still be a device array from a preceding
                # single-round call (the lazy-sync path in
                # _call_train)
                self._prev_change_words = jax.device_get(
                    self._prev_change_words)
            # screened family (ISSUE 16): the span's per-round
            # admitted rows replace the host survivor rows for
            # accounting (the bits transfer above already forced the
            # span, so this gather adds no sync) and journal one
            # `screened` event per round that screened anyone
            admitted_rows = None
            if metrics.admitted is not None:
                admitted_rows = np.asarray(
                    mh.gather_host(metrics.admitted), np.float32)
            # robust aggregation (ISSUE 17): per-round contributor
            # masks (billing) and aggregator stats (journal) ride the
            # span results like the admitted rows — the bits transfer
            # already forced the span, these gathers add no sync
            contrib_rows = None
            if metrics.contributors is not None:
                contrib_rows = np.asarray(
                    mh.gather_host(metrics.contributors), np.float32)
            agg_rows = None
            if metrics.agg_stats is not None:
                agg_rows = np.asarray(
                    mh.gather_host(metrics.agg_stats), np.float64)
            comm_rows = []
            for n in range(ids_host.shape[0]):
                surv_n = None if surv_all is None else surv_all[n]
                if admitted_rows is not None:
                    n_scr = None
                    if surv_n is not None:
                        n_scr = int((surv_n > 0).sum()
                                    - (admitted_rows[n] > 0).sum())
                        if n_scr > 0 and self.telemetry is not None:
                            self.telemetry.journal_event(
                                "screened", round=first + n,
                                n_screened=n_scr,
                                kind=(self.cfg.update_screen
                                      if self.cfg.update_screen
                                      != "off" else "finite"))
                    if (agg_rows is not None
                            and self.telemetry is not None):
                        self._journal_aggregator(first + n,
                                                 agg_rows[n])
                    if self.screen_ctl is not None and n_scr is not None:
                        self._observe_screening(first + n, n_scr,
                                                surv_n)
                    surv_n = (contrib_rows[n]
                              if contrib_rows is not None
                              else admitted_rows[n])
                if account:
                    d, u = self.accountant.record_round(
                        ids_host[n], self._prev_change_words,
                        survivors=surv_n)
                    download += d.sum()
                    upload += u.sum()
                    comm_rows.append((float(d.sum()),
                                      float(u.sum())))
                else:
                    # keep the change deque and staleness counters in
                    # sync (skipping only the popcount work) so a
                    # later accounted round doesn't misattribute
                    # downloads across the gap
                    self.accountant.advance_round(
                        ids_host[n], self._prev_change_words,
                        survivors=surv_n)
                    comm_rows.append(None)
                self._prev_change_words = bits_host[n]
                # compressor + privacy journaling (ISSUE 19) — same
                # per-round events as the unscanned commit path; the
                # budget raise lands after this round's accounting
                # lag advanced, the boundary a resume expects
                if (self.telemetry is not None
                        and comm_rows[-1] is not None):
                    self._journal_compressor(first + n,
                                             comm_rows[-1][1])
                if self.privacy is not None:
                    self._journal_privacy(first + n)

        # span-boundary telemetry export: ONE explicit device_get of
        # the [N, M] metric rows + [N, W] example counts, after the
        # bits transfer already forced span completion — telemetry adds
        # no sync points, and the explicit gathers keep the span
        # transfer-guard-clean (test_telemetry proves both). Runs after
        # the accounting loop so each journaled round carries its byte
        # totals (telemetry/journal `down_bytes`/`up_bytes`).
        sched_rows = [self._plan_active.pop(first + n, None)
                      for n in range(ids_host.shape[0])]
        if all(r is None for r in sched_rows):
            sched_rows = None
        tele_rows = None
        if self.telemetry is not None or self.control_bank is not None:
            tele_rows = (mh.gather_host(metrics.telemetry)
                         if self.cfg.telemetry else None)
        if self.telemetry is not None:
            counts_rows = mh.gather_host(metrics.num_examples)
            self.telemetry.on_span(
                first, ids_host, tele_rows, counts_rows,
                dispatch_s=handle.t_dispatched - handle.t_dispatch0,
                block_s=t_blocked - handle.t_dispatched,
                comm_rows=comm_rows, scheduled_rows=sched_rows)
            if self.state_store is not None:
                # per-span tier residency record (ISSUE 11). Under
                # Config.pipeline the deltas attribute the NEXT span's
                # already-staged motion to this span's record — a
                # bounded, documented skew (the journal is validated
                # on schema, not on per-span attribution)
                self.telemetry.journal_event(
                    "state_tier", first_round=first,
                    rounds=int(ids_host.shape[0]),
                    **self.state_store.take_journal_fields())
                for q in self.state_store.take_quarantine_events():
                    self.telemetry.journal_event(
                        "state_quarantine", first_round=first, **q)

        # controller bank (ISSUE 20): per-round commit observation on
        # the span's materialized metric rows (deterministic — a
        # replayed span re-observes identically), then the span-
        # cadence feed with the span's realized wall time (dispatch +
        # device execute; wall-clock, so its adjustments only ever
        # ride FUTURE fresh plans), then one drain of every queued
        # adjustment into `control` journal events — before the
        # injected-crash boundary below, matching the unscanned path
        # where committed rounds journal their adjustments before the
        # crash raises.
        if self.control_bank is not None:
            n_committed = int(ids_host.shape[0])
            for n in range(n_committed):
                self.control_bank.observe_commit(
                    first + n, self._control_signals(
                        None if tele_rows is None else tele_rows[n]))
            self.control_bank.feed_span(
                first + n_committed - 1, n_committed,
                float(t_blocked - handle.t_dispatch0))
            self._journal_control_events()

        if crash_at is not None:
            # every completed round's state/accounting landed above —
            # crash at the same boundary the unscanned path does
            self._journal_fault("crash_after", crash_at)
            raise InjectedFault(crash_at)

        losses = mh.gather_host(metrics.losses)
        mets = [mh.gather_host(m) for m in metrics.metrics]
        return [losses, *mets, download, upload]

    def _call_val(self, batch):
        """Multi-controller contract mirrors _call_train: `data`/`mask`
        are this process's shard rows; results are allgathered so every
        process returns the full per-shard metrics."""
        data, mask = batch
        loss, mets, count = self._eval_batch(
            self.server.ps_weights,
            tuple(self._feed(d) for d in data),
            self._feed(mask))
        return [mh.gather_host(loss), *[mh.gather_host(m) for m in mets],
                mh.gather_host(count)]


class FedOptimizer:
    """Holds param_groups for LR scheduling (reference FedOptimizer,
    fed_aggregator.py:384-458). The actual server update runs fused
    inside FedModel's round program; see module docstring."""

    def __init__(self, model: FedModel, cfg: Optional[Config] = None):
        self.model = model
        self.cfg = cfg or model.cfg
        self.param_groups = [{"lr": 0.0}]
        model._optimizer = self

    def step(self):
        """Host-side no-op kept for reference call-pattern parity; the
        weight update already happened inside model(batch)."""

    def zero_grad(self):
        raise NotImplementedError(
            "gradients are per-round temporaries in the fused design")

"""Configuration: the framework's single flat config namespace.

Flag-name-parity with the reference CLI (reference:
CommEfficient/utils.py:102-230 `parse_args`), so reference launch
commands work unmodified, but held in a typed dataclass instead of a
bare argparse namespace so it can be closed over as static jit config.

Static/hashable by design: a `Config` is frozen and usable as a jit
static argument; anything traced (learning rate, rng keys) is passed
separately.
"""
from __future__ import annotations

import argparse
import dataclasses
from dataclasses import dataclass, field
from typing import Optional, Tuple

# one Compressor plugin per mode (commefficient_tpu/compress): the
# five reference modes plus the ISSUE-19 plugins — powersgd (rank-r
# power-iteration factors) and dp_sketch (sketch transport under the
# Gaussian mechanism with Rényi budget accounting)
MODES = ("sketch", "true_topk", "local_topk", "fedavg", "uncompressed",
         "powersgd", "dp_sketch")
ERROR_TYPES = ("none", "local", "virtual")
DP_MODES = ("worker", "server")
SCREEN_MODES = ("off", "finite", "norm")
POISON_KINDS = ("nan", "inf", "scale")
# cross-client reduction of the jitted round (ISSUE 17,
# federated/round.py): mean is the reference FetchSGD sum/total;
# the robust tier computes per-cell order statistics over the
# [num_workers, ...] client update tables inside the round
AGGREGATORS = ("mean", "coord_median", "trimmed_mean", "norm_clip")
# scripted adversary kinds (utils/faults "byzantine" PRNG domain):
# sign_flip/scaled are per-client local corruptions; colluding and
# little_is_enough are COORDINATED crafted updates built from the
# honest cohort's statistics — finite and norm-plausible, the class
# admission screening provably cannot catch
ATTACKS = ("sign_flip", "scaled", "colluding", "little_is_enough")

# dataset -> num_classes (reference: utils.py:37-44); PERSONA is a
# language-modeling dataset so has no class count.
FED_DATASETS = {
    "CIFAR10": 10,
    "CIFAR100": 100,
    "EMNIST": 62,
    "ImageNet": 1000,
    "PERSONA": -1,
}

# default client counts when --num_clients is unset
# (reference: fed_aggregator.py:66-73)
DEFAULT_NUM_CLIENTS = {
    "EMNIST": 3500,
    "PERSONA": 17568,
}


def num_classes_of_dataset(dataset_name: str) -> int:
    return FED_DATASETS[dataset_name]


# Config.server_in_place's gate: 2**27 parameters, 512 MiB a copy
IN_PLACE_MIN_D = 1 << 27


@dataclass(frozen=True)
class Config:
    # meta (reference: utils.py:106-111)
    do_test: bool = False
    mode: str = "sketch"
    use_tensorboard: bool = False
    seed: int = 21

    # data/model (utils.py:114-139)
    model: str = "ResNet9"
    do_finetune: bool = False
    do_checkpoint: bool = False
    checkpoint_path: str = "./checkpoint"
    checkpoint_every: int = 0  # epochs between mid-run checkpoints; 0 = end only
    resume: bool = False
    finetune_path: str = "./finetune"
    finetuned_from: Optional[str] = None
    num_results_train: int = 2
    num_results_val: int = 2
    dataset_name: str = "CIFAR10"
    dataset_dir: str = "./dataset"
    do_batchnorm: bool = False
    nan_threshold: float = 999.0
    # dump a jax.profiler trace of the first training epoch into
    # <logdir>/profile (viewable in TensorBoard/Perfetto) — the TPU
    # equivalent of the reference's dormant cProfile scaffolding
    # (fed_aggregator.py:46-52; SURVEY.md §5 tracing row)
    do_profile: bool = False

    # observability (commefficient_tpu/telemetry, ISSUE 4). telemetry
    # is ON by default: the jitted round computes a fixed-shape named
    # f32 metric vector (telemetry/metrics.METRIC_NAMES — round loss,
    # update/error norms, survivor count, processed examples, realized
    # top-k, sketch estimate-residual proxy) that is exported to the
    # host only at span boundaries via explicit device_get. Disabling
    # it (--no_telemetry) traces the metric-free round program;
    # ServerState bits are identical either way (tests/test_telemetry).
    telemetry: bool = True
    # journal file path ("" = <run dir>/journal.jsonl): the structured
    # JSONL run record (telemetry/journal.py) — round/span metrics,
    # checkpoint saves, XLA compile events, retries, injected faults
    journal_path: str = ""
    # capture a jax.profiler trace of scanned-span indices [A, B)
    # ("" = off; requires --scan_rounds). Unlike --profile (whole first
    # epoch), this targets operator-selected steady-state spans
    profile_spans: str = ""
    # arm analysis/runtime.forbid_transfers around the drivers'
    # steady-state dispatch (every span/round after the first): any
    # implicit host<->device transfer — a hidden per-round sync, the
    # silent TPU performance cliff — raises
    debug_transfer_guard: bool = False
    # graftscope round-lifecycle tracing (ISSUE 13,
    # telemetry/trace.py). OFF by default: the tracer exists but
    # records nothing and adds zero journal writes (the only schema
    # change that lands regardless of this flag is the `mono`
    # timestamp every journal record carries). ON: monotonic-clock
    # spans bracket every HOST stage of the round lifecycle — plan
    # composition/broadcast, operand staging, dispatch, the
    # device-execute window at the dispatch/collect seam, tiered-state
    # restore/spill, collection/accounting, checkpoint saves, and each
    # writer thread's queue-wait + fsync — tagged with (round, span,
    # controller, thread) correlation keys, buffered in per-thread
    # rings, and flushed as batched `trace` journal events at span
    # boundaries. Zero traced-program changes either way (spans wrap
    # dispatch calls, never jitted code); scripts/trace_export.py
    # converts the journal to Perfetto-loadable Chrome trace JSON and
    # journal_summary.py reports per-stage p50/p95 + overlap
    # efficiency.
    trace: bool = False

    # compression (utils.py:142-147)
    k: int = 50000
    num_cols: int = 500000
    num_rows: int = 5
    num_blocks: int = 20
    do_topk_down: bool = False
    # download top-k budget, decoupled from the upload/server k
    # (0 = use k, the reference's single shared knob). The server's
    # update is k-sparse per round while a sparsely-participating
    # client accumulates MANY rounds of changes between downloads, so
    # the download budget that keeps staleness bounded is a multiple
    # of k.
    down_k: int = 0
    # wire dtype of the transmitted [r, c] sketch table (sketch mode
    # only): "f32" (default — the transport code path is the identity,
    # bit-identical to a build without the flag), "bf16", or "int8"
    # (symmetric per-row scales). Quantization rounds the shard's
    # client-sum table before the psum; the server's virtual error
    # feedback absorbs the rounding noise the same way it absorbs
    # sketch compression noise (ops/quant.py), telemetry's
    # estimate_residual metric gauges whether accuracy pays for it,
    # and the accountant bills upload bytes at the WIRE element size
    # (Config.upload_bytes).
    sketch_table_dtype: str = "f32"

    # optimization (utils.py:150-162)
    local_momentum: float = 0.9
    virtual_momentum: float = 0.0
    weight_decay: float = 5e-4
    num_epochs: float = 24.0
    num_fedavg_epochs: int = 1
    fedavg_batch_size: int = -1
    fedavg_lr_decay: float = 1.0
    error_type: str = "none"
    lr_scale: Optional[float] = None
    pivot_epoch: float = 5.0

    # fault tolerance (an extension beyond the reference, which assumes
    # every sampled client finishes every round and every run finishes
    # uninterrupted — neither holds in FetchSGD's target setting or on
    # preemptible TPU pods). client_dropout is the per-round Bernoulli
    # probability that a sampled client FAILS to complete the round:
    # its upload is excluded from aggregation (survivor-count
    # reweighting), its persistent error/velocity/stale-weight rows
    # stay bit-untouched, and accounting charges it nothing. The draw
    # is deterministic in (seed, round), so crash->resume replays it
    # exactly. 0.0 keeps the engine on the mask-free program — the
    # machinery costs nothing when disabled. Tests inject explicit
    # per-round schedules instead (utils/faults.FaultSchedule).
    client_dropout: float = 0.0
    # buffer donation for the jitted round dispatch (ISSUE 7: the
    # graftaudit donation audit's first applied finding). When on —
    # the default — the dead-after-dispatch round inputs are donated
    # to XLA so their HBM is reused for the matching outputs in place:
    # the scanned span donates ServerState AND the per-client state
    # rows (run_rounds only ever assigns state from the span's
    # RESULT), the per-round path donates the client rows only
    # (FedModel._call_train reads the previous ps_weights AFTER
    # dispatch for the lagged accounting bitset, so ServerState must
    # survive — the justified exception graftaudit documents). At the
    # EMNIST/PERSONA populations the error-feedback block is the
    # dominant allocation (3500 x 6.6M f32 ≈ 92 GB across a pod), so
    # un-donated dispatch transiently doubles it. Semantics are
    # bit-identical either way (aliasing only; tests/test_audit.py
    # proves resume bit-exactness) — but donated inputs are INVALID
    # after the call: generic callers that re-dispatch from a retained
    # state object (benchmark timing loops) must disable this, and a
    # donated span dispatch that fails mid-execute can no longer be
    # transparently retried (utils/retry), which is what
    # --no_donate_round_state is for on flaky preemptible pods.
    donate_round_state: bool = True
    # straggler (slow-client) modeling beyond binary dropout: each
    # sampled client is a straggler with probability straggler_rate;
    # a straggler draws a WORK FRACTION uniform in
    # [straggler_min_work, 1) — deterministic in (seed, round), same
    # replay contract as client_dropout (utils/faults.
    # straggler_work_fractions). The fraction becomes a per-client
    # completed-examples budget (completed local SGD steps for
    # fedavg) inside the jitted round, and aggregation weights by
    # examples actually processed (FedNova-style) so partial uploads
    # don't bias the average. A fraction below straggler_cutoff
    # degrades to the dropout path: state rows bit-untouched,
    # accounting charges nothing. 0.0 keeps the engine on the
    # work-free program — the machinery costs nothing when disabled.
    straggler_rate: float = 0.0
    straggler_min_work: float = 0.1
    straggler_cutoff: float = 0.0
    # numeric-integrity layer (ISSUE 16, federated/round.py screened
    # programs). update_screen is the in-round update ADMISSION policy:
    # "off" — the default, bit-identical to a build without the
    # feature (default configs trace the original three round
    # programs) — "finite" screens any client whose local update
    # carries a NaN/Inf, "norm" additionally screens norm outliers
    # (update l2 > screen_norm_mult x the cohort's median l2 over
    # surviving, measurable clients; rounds with no measurable
    # survivor admit everyone, so the screen is zero-survivor-safe).
    # A screened client takes EXACTLY the dropped-client path — state
    # rows bit-untouched, survivor-count reweighting, survivor-only
    # accounting — so screening composes with dropout, stragglers,
    # deadlines, and async admission for free.
    update_screen: str = "off"
    screen_norm_mult: float = 5.0
    # value-fault INJECTION (utils/faults.poison_mask): each sampled
    # client's update is corrupted with this per-round probability —
    # deterministic in (seed, round) on its own PRNG domain, same
    # replay contract as client_dropout. poison_kind picks the
    # corruption: nan / inf overwrite the transmitted update, scale
    # multiplies it by 2^40 (a finite explosion only the norm screen
    # catches). 0.0 keeps every default program untouched.
    poison_rate: float = 0.0
    poison_kind: str = "nan"
    # Byzantine-robust aggregation tier (ISSUE 17, federated/round.py
    # robust programs). aggregator replaces the cross-client mean with
    # a robust reduction computed INSIDE the jitted round, composed
    # with the admission mask (screened/dropped clients are excluded
    # from the order statistics; zero-survivor safe): coord_median is
    # the per-cell coordinate median over admitted client tables,
    # trimmed_mean drops the trim_beta fraction from each end of every
    # cell's order statistics before the FedNova-weighted mean,
    # norm_clip rescales each client's update to at most the cohort
    # median l2 before the ordinary weighted mean (the cheap option).
    # "mean" — the default — keeps the traced round programs
    # bit-identical to a build without the feature.
    aggregator: str = "mean"
    trim_beta: float = 0.2
    # scripted adversary harness (utils/faults.byzantine_mask, its own
    # "byzantine" PRNG domain — deterministic in seed+round, same
    # replay contract as client_dropout/poison). Each sampled client
    # is an attacker with probability byzantine_rate; `attack` picks
    # the crafted update (ATTACKS above). 0.0 keeps every default
    # program untouched.
    byzantine_rate: float = 0.0
    attack: str = "sign_flip"
    # plan-driven adaptive screening (scheduler.AdaptiveScreenController):
    # with target_screened_rate >= 0 the norm-screen threshold
    # screen_norm_mult becomes a per-round TRACED operand adjusted
    # toward the target from the journaled per-round screened-rate —
    # each adjustment rides the journaled RoundPlan (coordinator-
    # broadcast under --plan_transport, replayed not recomputed on
    # takeover) so crash->resume reproduces the exact threshold
    # trajectory. Negative (the default) keeps the static threshold
    # and the PR-16 traced programs byte-identical.
    target_screened_rate: float = -1.0
    screen_adapt_step: float = 0.5
    screen_mult_min: float = 1.5
    screen_mult_max: float = 64.0
    # plan-riding controller bank (control/, ISSUE 20): three
    # self-tuning loops on the ISSUE-17 pattern — every adjustment is
    # bounded, f32-rounded, rides the journaled RoundPlan (`controls`
    # wire dict), and is installed (never recomputed) by followers and
    # replayed rounds. All off by default: make_bank returns None and
    # the loop is bit-identical to a pre-controller build.
    #
    # cohort speed-matching (control/speed.py): clients whose
    # examples/sec EMA falls below speed_ratio x cohort-median get a
    # work fraction < 1 min-composed onto plan.work, which the async
    # admission buffer defers into an --async_admit_rounds slot; the
    # ratio is nudged so the deferred fraction tracks
    # speed_match_target, clamped to [speed_ratio_min,
    # speed_ratio_max] (max < 1 — "slow" must mean strictly slower
    # than the median).
    speed_match: bool = False
    speed_match_target: float = 0.25
    speed_match_step: float = 0.25
    speed_ratio: float = 0.5
    speed_ratio_min: float = 0.25
    speed_ratio_max: float = 0.9
    # adaptive span cadence (control/span.py): comma-separated span
    # lengths ("1,2,4") the scanned staging loop may flush at; each
    # entry's program traces ONCE at warmup (the palette is the whole
    # shape vocabulary — steady state stays zero-recompile) and the
    # per-entry seconds-per-round EMA picks the steady-state length.
    # Must include 1 (the stream tail decomposes greedily over the
    # palette). Empty = static --scan_span, the default.
    scan_span_palette: str = ""
    # adaptive staleness decay (control/staleness.py): the
    # estimate_residual metric drives async_staleness_decay between
    # [staleness_decay_min, staleness_decay_max] — residual above
    # staleness_target discounts late admissions harder.
    adapt_staleness: bool = False
    staleness_target: float = 0.3
    staleness_step: float = 0.25
    staleness_decay_min: float = 0.2
    staleness_decay_max: float = 0.95
    # finite-frontier auto-rollback (the drivers' numeric_trip
    # handler): after a non-finite update/error-l2 trips telemetry and
    # the run rolls back to the newest finite checkpoint, screening is
    # FORCE-ENABLED for this many rounds so the replayed fault is
    # admitted out instead of re-tripping; bounded by
    # max_numeric_rollbacks trips per run, after which the driver
    # fails loud instead of thrashing.
    rollback_screen_rounds: int = 8
    max_numeric_rollbacks: int = 2
    # keep the newest k rotated mid-run checkpoints (utils/checkpoint.
    # save_rotating); older ones are pruned after each atomic save
    keep_checkpoints: int = 3
    # ALSO prune rotated checkpoints older than this wall-clock age in
    # hours (0 = age pruning off). Long preemptible-pod runs rotate
    # slowly near the end of an epoch; age pruning bounds disk growth
    # by time, not count. The manifest's `latest` entry is never
    # age-pruned, so resume always has a target.
    ckpt_max_age_hours: float = 0.0
    # scanned-path (--scan_rounds) checkpoint cadence in SPANS: with
    # checkpoint_every on, save a rotated checkpoint every k-th span
    # boundary (a span is the atomic commit unit — a preemption
    # mid-span loses back to the last boundary, so 1 bounds the loss
    # of a kill at any instant to one span). Each save is a full
    # server+client gather plus a disk write; short spans on a big
    # model can make every-boundary saving dominate, so raise this to
    # bound the save rate (preemption loss grows to k spans), or 0 to
    # keep only the epoch-cadence saves.
    ckpt_every_spans: int = 1

    # parallelization (utils.py:165-180). `port` kept for CLI parity but
    # unused: there is no process-group rendezvous in a single-program
    # SPMD runtime (reference needed it at fed_aggregator.py:161-164).
    port: int = 5315
    # run each epoch's rounds as one scanned device program (a TPU-only
    # capability; the reference's process/queue round-trip per round
    # cannot be batched this way). scan_span bounds the staged
    # [N, W, B, ...] device arrays by flushing every `scan_span` rounds
    # (0 = whole epoch in one program; set a span at ImageNet scale —
    # staging memory is span * num_workers * B * example_bytes).
    scan_rounds: bool = False
    scan_span: int = 0
    num_clients: Optional[int] = None
    num_workers: int = 1
    # tensor-parallel degree over the mesh's `model` axis (an extension
    # beyond the reference, whose only parallelism is one worker
    # process per GPU): >1 lays devices out as (clients, model) and
    # GSPMD-partitions each client's fwd/bwd per parallel/tp.py
    model_parallel: int = 1
    # lay the clients axis slice-major over DCN (emulated grouping off
    # real multi-slice hardware; parallel/mesh.py
    # make_multihost_client_mesh). 1 = flat single-slice mesh; real
    # slice topology is auto-detected either way
    num_slices: int = 1
    # multi-HOST runtime (the reference's PS + worker process topology,
    # fed_aggregator.py:143-164, as multi-controller SPMD): --multihost
    # calls jax.distributed.initialize before any backend use. On TPU
    # pods the coordinator/process grid is auto-detected; off-pod (CPU
    # grids, tests) pass all three of coordinator_address /
    # num_processes / process_id explicitly.
    multihost: bool = False
    coordinator_address: str = ""
    num_processes: int = 0
    process_id: int = -1
    # run client forward/backward in bfloat16 (f32 master weights and
    # f32 server/compression state; see client.make_flat_grad_fn) —
    # the MXU's fast path, an extension over the reference's fp32 CUDA
    do_bf16: bool = False
    # rematerialize transformer blocks on backward (GPT2 workload):
    # O(1)-block activation memory for ~1/3 extra FLOPs
    do_remat: bool = False
    # cap on the static per-client batch dim when local_batch_size=-1
    # (whole-client batches). Uncapped, fedavg at ImageNet scale stages
    # max(data_per_client) examples per client slot (~2.4 GB f32 at
    # 1300x224x224x3) — the cap bounds staging memory; clients with
    # more data participate in consecutive rounds on successive chunks
    # (a documented divergence: the reference instead serializes whole
    # clients one at a time per GPU, fed_worker.py:68-77).
    max_local_batch: int = -1
    device: str = "tpu"
    num_devices: int = 1
    share_ps_gpu: bool = False
    do_iid: bool = False
    train_dataloader_workers: int = 0
    val_dataloader_workers: int = 0

    # GPT2 (utils.py:183-207)
    model_checkpoint: str = "gpt2"
    num_candidates: int = 2
    max_history: int = 2
    local_batch_size: int = 8
    valid_batch_size: int = 8
    microbatch_size: int = -1
    lm_coef: float = 1.0
    mc_coef: float = 1.0
    max_grad_norm: Optional[float] = None
    personality_permutations: int = 1
    eval_before_start: bool = False

    # differential privacy (utils.py:210-214)
    do_dp: bool = False
    dp_mode: str = "worker"
    l2_norm_clip: float = 1.0
    noise_multiplier: float = 0.0

    # --- Compressor plugin knobs (ISSUE 19, commefficient_tpu/compress)
    # powersgd: rank of the per-client P/Q power-iteration factors —
    # the wire carries (m + n) * rank floats for the near-square
    # [m, n] factorization of the flat [grad_size] update
    powersgd_rank: int = 2
    # dp_sketch: the Gaussian mechanism on the sketch table. dp_clip
    # is the per-client Frobenius sensitivity bound on the count-
    # scaled table; dp_noise_mult the noise multiplier (noise std =
    # dp_noise_mult * dp_clip on the AGGREGATE, once per round);
    # dp_target_epsilon the fail-loud budget ceiling at dp_delta
    # (0 = track epsilon in the journal but never fail). Epsilon is
    # tracked by the Rényi accountant (compress/privacy.py) and
    # journaled per round as `privacy` events.
    dp_clip: float = 1.0
    dp_noise_mult: float = 0.0
    dp_target_epsilon: float = 0.0
    dp_delta: float = 1e-5

    # round scheduling (commefficient_tpu/scheduler, ISSUE 5): the
    # telemetry substrate's consumer. `sampler` picks the participant
    # policy — "uniform" is BIT-IDENTICAL to the pre-scheduler draw
    # (the default), "throughput" deprioritizes chronically slow
    # clients by their measured EMA examples/sec with an exploration
    # floor (`explore_floor`: every alive client keeps at least
    # floor/num_alive selection probability per slot so it keeps
    # getting measured). Throughput draws live on their own PRNG
    # domain, distinct from the dropout/straggler streams.
    sampler: str = "uniform"
    explore_floor: float = 0.1
    # deadline-driven rounds: 0 = off; otherwise each round's
    # wall-clock deadline is this quantile of the participants'
    # measured time estimates, and participants estimated past it get
    # work fractions deadline/estimate (floored at deadline_min_work)
    # on the EXISTING straggler work operand — deadline aggregation
    # stays inside the jitted round, three traced programs unchanged.
    # Unmeasured participants are never truncated (scheduler/deadline).
    deadline_quantile: float = 0.0
    deadline_min_work: float = 0.1
    # over-provisioning: sample ceil(target / expected-survival-rate)
    # participants (capped at num_workers) so EXPECTED survivors hit
    # this target; surplus compiled slots ride as survivor-mask zeros
    # (bit-exactly the dropped-client path). 0 = no target: fill every
    # slot, the pre-scheduler behavior.
    target_survivors: int = 0

    # pipelined round engine (ISSUE 10). OFF by default — the default
    # path is bit-identical to the pre-feature synchronous loop (the
    # pipelining machinery is never constructed). When on:
    #   * the scanned staging loop double-buffers dispatch
    #     (training/scanloop.py): span t+1's host staging — sampler
    #     draws, batch stacking, fault operands, explicit device
    #     placement — overlaps span t's device execution, and the
    #     span's accounting/journal/checkpoint commit one span late
    #     (FedModel.dispatch_rounds / collect_rounds);
    #   * journal appends and span-boundary checkpoint serialization
    #     move onto bounded-queue writer threads
    #     (telemetry/journal.RunJournal(async_writer=True),
    #     utils/checkpoint.AsyncCheckpointWriter) with flush-on-close
    #     and drain-at-crash — atomic-rename and torn-tail semantics
    #     unchanged;
    #   * the scanned span jit does NOT donate its state operands
    #     (round.py): the span-boundary checkpoint persists span t's
    #     state while span t+1 — which would otherwise consume those
    #     buffers in place — is already in flight, so double buffering
    #     transiently doubles state HBM (the price of the overlap).
    # Single-controller only for now (the writer threads and the
    # deferred commit would need cross-process barriers).
    pipeline: bool = False
    # buffered async aggregation (ISSUE 10): admit a straggler's late
    # contribution into round t+k instead of truncating it at round
    # t's deadline. A sampled client whose work fraction is below 1.0
    # (random straggler draw, FaultSchedule.slow, or a deadline
    # truncation) is DEFERRED: excluded from round t exactly like a
    # dropped client (no upload, state rows bit-untouched, accounting
    # charges nothing), then merged into round t+k's cohort operands
    # with its work fraction discounted by async_staleness_decay**k —
    # the FedNova-style processed-example reweighting the work operand
    # already implements turns that into a staleness-discounted
    # aggregation weight. Zero new traced programs: admission reuses
    # the existing dropout/straggler operand treedefs
    # (federated/async_agg.py). 0 = off (the synchronous straggler
    # path); k=0 via the buffer API is proven bit-identical to it.
    async_admit_rounds: int = 0
    # per-round staleness decay of a late-admitted contribution's
    # work fraction: weight = decay**rounds_late (1.0 = no discount)
    async_staleness_decay: float = 0.5

    # tiered cold client state (ISSUE 11). "device" — the default —
    # keeps the full [padded_population, D] client-state blocks
    # sharded in device HBM (bit-identical to the pre-feature
    # program: the tier machinery is never constructed). "host" caps
    # the device-resident rows at an LRU working set of
    # `state_working_set` recently-active clients; the long tail of
    # cold rows lives on the host (optionally disk-backed via
    # `state_spill_dir`), and the cohort-gather/scatter-back
    # state-motion pair moves rows between tiers: a sampled client
    # outside the working set is RESTORED into a device slot before
    # its round (through the same scatter program, as host-built
    # cohort rows) and the evicted victim's row is SPILLED to the
    # host tier off the critical path (the same gather program + an
    # async device->host copy on a bounded-queue writer thread — the
    # ISSUE-10 persistence pattern). The three round programs still
    # see only [num_workers, D] cohort operands (AU004 strict keeps
    # them honest), results are bit-identical to state_tier=device
    # (f32 rows round-trip the host exactly), and device HBM for
    # client state is O(working set) regardless of the population.
    # Single-controller only for now (the host tail is process-local;
    # per-process sharded tails are a ROADMAP opening).
    state_tier: str = "device"
    # device-HBM working-set size in client rows (state_tier=host):
    # the LRU keeps at most this many clients' state rows resident
    # (rounded up to the mesh's clients axis). Must be >= num_workers
    # (a round's whole cohort must fit), and on the scanned path
    # >= the distinct clients of one span (the span executes as one
    # device program, so its rows must all be resident at once —
    # FedModel raises an actionable error otherwise).
    state_working_set: int = 0
    # optional disk backing for the host tail (state_tier=host): cold
    # rows live in per-block f32 memmaps under this directory instead
    # of process RAM — sparse files, so untouched rows cost nothing.
    # Scratch state: rebuilt from the checkpoint's crows_* rows on
    # resume, never loaded across runs.
    state_spill_dir: str = ""

    # coordinator-broadcast control plane (ISSUE 12,
    # parallel/plantransport.py). "" — the default — attaches no
    # transport: non-default scheduling stays single-controller and
    # every code path is bit-identical to the pre-feature build.
    # "collective" attaches the production HostCollectiveTransport
    # (one fixed-size one-to-all host collective per round + a digest
    # allgather): the coordinator broadcasts each round's RoundPlan,
    # every process installs the RECEIVED plan, and Config.validate
    # then accepts throughput sampling / deadlines / async admission
    # in multihost runs. "emulated" replaces the run's scheduler with
    # an in-process N-controller harness (plan_controllers lockstep
    # controllers over an in-memory bus) — the CI surface for the
    # fault story, since this container cannot run multi-process jax.
    plan_transport: str = ""
    plan_controllers: int = 2
    # writer-thread watchdog (ISSUE 12 satellite): flush/drain timeout
    # in seconds for the three bounded-queue writers (journal,
    # checkpoint, state spill). 0 = wait forever (the old behavior);
    # positive turns a hung fsync into a TimeoutError NAMING the stuck
    # writer instead of a silent hang at crash-time drain.
    writer_drain_timeout_s: float = 0.0

    # set after model construction (reference mutates args.grad_size at
    # fed_aggregator.py:88; we return a new frozen Config instead)
    grad_size: int = 0
    # set by a driver whose model has expert layers (like grad_size, no
    # flag): their number, dense layers not counted. The loss's last
    # metric is then each client's expert load [layers, held + 4], and
    # the round appends seven counters an expert layer to its telemetry
    # vector (telemetry/metrics.expert_load_vector).
    expert_load_layers: int = 0

    # --- derived helpers -------------------------------------------------
    def replace(self, **kw) -> "Config":
        return dataclasses.replace(self, **kw)

    @property
    def compressor(self):
        """The registered Compressor plugin for this mode (ISSUE 19,
        commefficient_tpu/compress). Lazy import: compress imports
        this module for the MODES coverage assert, so the dependency
        must point compress -> config at module level and
        config -> compress only at property-call time."""
        from commefficient_tpu.compress import get_compressor
        return get_compressor(self.mode)

    @property
    def state_shape(self) -> Tuple[int, ...]:
        """Shape of the transmitted/accumulated quantity for this mode
        (reference: fed_aggregator.py:116-121,400-405; delegated to
        the mode's Compressor plugin)."""
        return self.compressor.state_shape(self)

    @property
    def upload_floats(self) -> int:
        """Floats uploaded per participating client per round
        (reference: fed_aggregator.py:291-299; delegated to the
        mode's Compressor plugin)."""
        return self.compressor.wire_floats(self)

    @property
    def upload_bytes(self) -> int:
        """Bytes uploaded per participating client per round AT THE
        WIRE DTYPE — the quantity the accountant bills and journals
        (ISSUE 6 accounting satellite; delegated to the mode's
        Compressor plugin). For sketch mode this is the [r, c] table
        at sketch_table_dtype's element size (plus int8's per-row f32
        scales); every other plugin transmits f32, so it is
        4 x upload_floats exactly as before."""
        return self.compressor.wire_bytes(self)

    @property
    def defer_sketch_encode(self) -> bool:
        """Sketch linearity optimization: when nothing nonlinear
        touches the per-client compressed quantity — no per-client DP
        clip/noise, no per-client table clip (and sketch mode never has
        per-client momentum/error state, see validate()) — the sum of
        per-client sketches equals the sketch of the summed gradient,
        so the round engine encodes ONCE per mesh shard after the local
        client sum instead of once per client (8 clients/shard -> 8x
        less encode work; measured in PERF.md)."""
        return (self.mode == "sketch" and not self.do_dp
                and self.max_grad_norm is None)

    @property
    def fused_client_backward(self) -> bool:
        """Backward-pass linearity optimization: when every per-client
        transmit is a LINEAR function of that client's gradient — no
        per-client DP/clipping, no per-client momentum/error state, no
        per-client weight staleness (topk_down), and no local_topk
        sparsification — the shard's summed transmit equals the
        gradient of the count-weighted summed loss, so the round
        engine runs ONE backward pass over all the shard's clients
        instead of a vmapped per-client backward. That removes the
        [W_shard, D] per-client gradient materialization (2 GB at
        GPT2-small x 4 clients) and lets XLA batch the weight-grad
        matmuls across clients; per-client losses/metrics still come
        from the (cheap) per-client forward values. Microbatching is
        gated out: the fused backward sees all clients' examples at
        once, which is exactly what microbatch_size exists to avoid."""
        return (self.mode in ("sketch", "uncompressed", "true_topk")
                and not self.do_dp and self.max_grad_norm is None
                and self.local_momentum == 0
                and self.error_type != "local"
                and not self.do_topk_down
                and self.microbatch_size <= 0)

    @property
    def server_in_place(self) -> bool:
        """Above IN_PLACE_MIN_D parameters the per-round program
        updates the server state in place: it takes the ServerState
        donated, packs the round's change bits itself (as the scanned
        span program always has) and hands them back beside the
        metrics, so no second copy of the weights is kept for the
        accounting; and a mode whose server never touches Verror
        (`server_error_unused`) keeps a one-element placeholder in its
        place. At 4 bytes a parameter each of those copies is over
        half a gigabyte there; at D = 6.6e8 weights, momentum, error,
        their three outputs and the kept weights would be 18 GB of a
        16 GB chip. D-based, not a flag: below the gate (every model
        up to GPT2-small) the programs are what they were."""
        return self.grad_size >= IN_PLACE_MIN_D

    @property
    def server_error_unused(self) -> bool:
        """The modes whose server update hands Verror through
        untouched (federated/server.py: _uncompressed, _fedavg,
        _local_topk)."""
        return self.mode in ("uncompressed", "fedavg", "local_topk")

    @property
    def robust_aggregation(self) -> bool:
        """True when the cross-client reduction is a robust order
        statistic (ISSUE 17). Robust rounds need PER-CLIENT update
        tables on device, so they always trace the screened program
        family (the per-client path) even with screening off.

        trimmed_mean with trim_beta == 0.0 trims nothing, so it is
        statically strength-reduced to the plain mean program: that
        keeps the inert setting bit-identical to ``--aggregator mean``
        even under defer_sketch_encode, where the mean path encodes
        the client SUM once while the robust path must encode every
        client before the order statistics (a ~1-ULP accumulation-
        order difference otherwise)."""
        if self.aggregator == "trimmed_mean" and self.trim_beta == 0.0:
            return False
        return self.aggregator != "mean"

    @property
    def adaptive_screen(self) -> bool:
        """True when the norm-screen threshold is the plan-carried
        traced operand the AdaptiveScreenController adjusts (ISSUE
        17); False keeps the static screen_norm_mult constant folded
        into the traced programs exactly as PR 16 shipped them."""
        return (self.target_screened_rate >= 0.0
                and self.update_screen == "norm")

    @property
    def span_palette(self) -> tuple:
        """Parsed --scan_span_palette: ascending unique span lengths,
        () when the adaptive span-cadence controller is off. Ascending
        order is the warmup trace order AND the argmin tie-break
        (np.argmin takes the first minimum → the shortest span wins a
        cadence tie), so the trajectory is deterministic in the flag
        string."""
        s = self.scan_span_palette.strip()
        if not s:
            return ()
        return tuple(sorted({int(tok) for tok in s.split(",")
                             if tok.strip()}))

    @property
    def control_loop(self) -> bool:
        """True when any bank-managed controller is enabled (the
        drivers then build plans every round so adjustments can ride
        them — control.make_bank returns non-None exactly when this
        does)."""
        return bool(self.speed_match or self.span_palette
                    or self.adapt_staleness)

    def resolved_num_clients(self, dataset_num_clients: Optional[int] = None) -> int:
        if self.num_clients is not None:
            return self.num_clients
        if dataset_num_clients is not None:
            return dataset_num_clients
        if self.dataset_name in DEFAULT_NUM_CLIENTS:
            return DEFAULT_NUM_CLIENTS[self.dataset_name]
        raise ValueError(
            f"num_clients must be given for dataset {self.dataset_name}"
        )

    def validate(self) -> "Config":
        """Config invariants; the scattered asserts of the reference
        (utils.py:225-228, fed_aggregator.py:484-486,573-576,
        fed_worker.py:62-63,221-228) centralized into one place."""
        if self.mode not in MODES:
            raise ValueError(f"unknown mode {self.mode}")
        if self.error_type not in ERROR_TYPES:
            raise ValueError(f"unknown error_type {self.error_type}")
        if self.dp_mode not in DP_MODES:
            raise ValueError(f"unknown dp_mode {self.dp_mode}")
        if self.mode == "fedavg":
            if self.local_batch_size != -1:
                raise ValueError("fedavg requires local_batch_size == -1")
            if self.local_momentum != 0:
                raise ValueError("fedavg requires local_momentum == 0")
            if self.error_type != "none":
                raise ValueError("fedavg requires error_type == none")
        if self.mode == "true_topk" and self.error_type != "virtual":
            raise ValueError("true_topk requires error_type == virtual")
        if self.mode == "local_topk" and self.error_type == "virtual":
            raise ValueError("local_topk cannot use virtual error")
        if self.mode == "sketch":
            if self.error_type == "local" and self.virtual_momentum != 0:
                raise ValueError("sketch+local error requires virtual_momentum=0")
            if self.error_type == "virtual" and self.local_momentum != 0:
                raise ValueError("sketch+virtual error requires local_momentum=0")
            if self.error_type == "local":
                raise ValueError(
                    "sketch mode cannot use per-client local error accumulation "
                    "(reference asserts this at fed_worker.py:221-222)"
                )
            if self.local_momentum != 0:
                raise ValueError(
                    "sketch mode cannot use local momentum "
                    "(reference asserts this at fed_worker.py:227-228)"
                )
        if self.mode == "uncompressed" and self.error_type == "local":
            raise ValueError(
                "uncompressed cannot use local error accumulation "
                "(reference asserts this at fed_worker.py:221-222)"
            )
        if not 0.0 <= self.client_dropout < 1.0:
            raise ValueError(
                f"client_dropout={self.client_dropout} must be in [0, 1) "
                "(1.0 would drop every client every round)")
        if not 0.0 <= self.straggler_rate <= 1.0:
            raise ValueError(
                f"straggler_rate={self.straggler_rate} must be in [0, 1]")
        if not 0.0 < self.straggler_min_work <= 1.0:
            raise ValueError(
                f"straggler_min_work={self.straggler_min_work} must be "
                "in (0, 1] (0 would draw clients that do no work at "
                "all — that's dropout, use client_dropout/cutoff)")
        if not 0.0 <= self.straggler_cutoff <= 1.0:
            raise ValueError(
                f"straggler_cutoff={self.straggler_cutoff} must be in "
                "[0, 1] (fractions below it degrade to dropout)")
        if self.update_screen not in SCREEN_MODES:
            raise ValueError(
                f"unknown update_screen {self.update_screen!r} "
                "(choices: off, finite, norm — federated/round.py "
                "screened programs)")
        if self.screen_norm_mult <= 1.0:
            raise ValueError(
                f"screen_norm_mult={self.screen_norm_mult} must be "
                "> 1 (an update AT the cohort median is by definition "
                "not an outlier; <= 1 would screen half the cohort "
                "every round)")
        if not 0.0 <= self.poison_rate < 1.0:
            raise ValueError(
                f"poison_rate={self.poison_rate} must be in [0, 1) "
                "(1.0 would corrupt every client every round — no "
                "finite update would ever survive the screen)")
        if self.poison_kind not in POISON_KINDS:
            raise ValueError(
                f"unknown poison_kind {self.poison_kind!r} "
                "(choices: nan, inf, scale — utils/faults)")
        if self.aggregator not in AGGREGATORS:
            raise ValueError(
                f"unknown aggregator {self.aggregator!r} (choices: "
                f"{', '.join(AGGREGATORS)} — federated/round.py "
                "robust programs)")
        if not 0.0 <= self.trim_beta < 0.5:
            raise ValueError(
                f"trim_beta={self.trim_beta} must be in [0, 0.5) "
                "(trimming half the cohort from EACH end leaves no "
                "client to average)")
        if not 0.0 <= self.byzantine_rate < 1.0:
            raise ValueError(
                f"byzantine_rate={self.byzantine_rate} must be in "
                "[0, 1) (1.0 leaves no honest client for the robust "
                "statistics to anchor on)")
        if self.attack not in ATTACKS:
            raise ValueError(
                f"unknown attack {self.attack!r} (choices: "
                f"{', '.join(ATTACKS)} — utils/faults adversary "
                "harness)")
        if self.byzantine_rate > 0 and self.poison_rate > 0:
            raise ValueError(
                "--byzantine_rate and --poison_rate are mutually "
                "exclusive: both ride the per-client fault operand, "
                "and a slot cannot be simultaneously an accidental "
                "value fault and a scripted adversary")
        if self.target_screened_rate >= 0:
            if self.update_screen != "norm":
                raise ValueError(
                    "--target_screened_rate adapts the NORM-screen "
                    "threshold and requires --update_screen norm "
                    "(finite screening has no threshold to adapt)")
            if self.target_screened_rate >= 1.0:
                raise ValueError(
                    f"target_screened_rate={self.target_screened_rate}"
                    " must be < 1 (screening the whole cohort every "
                    "round is a dead run)")
        if self.screen_adapt_step <= 0:
            raise ValueError(
                "screen_adapt_step must be > 0 (the multiplicative "
                "adjustment factor is 1 + step)")
        if not 1.0 < self.screen_mult_min <= self.screen_mult_max:
            raise ValueError(
                f"need 1 < screen_mult_min={self.screen_mult_min} <= "
                f"screen_mult_max={self.screen_mult_max} (same > 1 "
                "floor as screen_norm_mult)")
        if self.rollback_screen_rounds < 1:
            raise ValueError(
                "rollback_screen_rounds must be >= 1: a rollback that "
                "resumes with zero forced-screen rounds replays the "
                "same non-finite update and trips forever")
        if self.max_numeric_rollbacks < 0:
            raise ValueError(
                "max_numeric_rollbacks must be >= 0 (0 = a numeric "
                "trip fails loud immediately, no rollback)")
        if self.keep_checkpoints < 1:
            raise ValueError("keep_checkpoints must be >= 1")
        if self.ckpt_max_age_hours < 0:
            raise ValueError(
                "ckpt_max_age_hours must be >= 0 (0 = age pruning off)")
        if self.ckpt_every_spans < 0:
            raise ValueError(
                "ckpt_every_spans must be >= 0 (0 = no span-boundary "
                "saves, only the epoch cadence)")
        if self.profile_spans:
            # parse for side effect: a malformed spec fails at config
            # time with the flag named, not mid-run
            from commefficient_tpu.telemetry import parse_profile_spans
            parse_profile_spans(self.profile_spans)
            if not self.scan_rounds:
                # spans only exist on the scanned path — without it the
                # capture would silently never happen
                raise ValueError(
                    "--profile_spans requires --scan_rounds (span "
                    "indices select SCANNED spans; use --profile for "
                    "the per-round path's whole-first-epoch trace)")
            if not self.telemetry:
                # the capture is driven by the TelemetrySession that
                # --no_telemetry skips constructing
                raise ValueError(
                    "--profile_spans requires telemetry (drop "
                    "--no_telemetry: the session drives the capture)")
        if self.trace and not self.telemetry:
            # the tracer flushes through the telemetry session's
            # journal; without the session nothing would ever drain
            # the rings — fail loud like --profile_spans
            raise ValueError(
                "--trace requires telemetry (drop --no_telemetry: "
                "the session drains the trace rings into the journal)")
        if self.sampler not in ("uniform", "throughput"):
            raise ValueError(
                f"unknown sampler {self.sampler!r} (choices: uniform, "
                "throughput — commefficient_tpu/scheduler)")
        if not 0.0 <= self.explore_floor <= 1.0:
            raise ValueError(
                f"explore_floor={self.explore_floor} must be in [0, 1] "
                "(1.0 degenerates throughput sampling to uniform)")
        if not 0.0 <= self.deadline_quantile <= 1.0:
            raise ValueError(
                f"deadline_quantile={self.deadline_quantile} must be "
                "in [0, 1] (0 = no deadline)")
        if not 0.0 < self.deadline_min_work <= 1.0:
            raise ValueError(
                f"deadline_min_work={self.deadline_min_work} must be "
                "in (0, 1] — zero work is dropout, not a deadline "
                "truncation (use straggler_cutoff for degradation)")
        if self.target_survivors < 0:
            raise ValueError("target_survivors must be >= 0 (0 = fill "
                             "every participant slot)")
        if self.target_survivors > self.num_workers:
            raise ValueError(
                f"target_survivors={self.target_survivors} exceeds "
                f"num_workers={self.num_workers}: a round cannot "
                "produce more survivors than compiled participant "
                "slots")
        if not self.telemetry and (self.sampler != "uniform"
                                   or self.deadline_quantile > 0):
            # without the telemetry session nothing ever feeds the
            # throughput tracker, so these policies would silently
            # degenerate (uniform-with-floor sampling, a deadline that
            # never fires) — same fail-loud rule as --profile_spans.
            # --target_survivors is fine: its survival estimate falls
            # back to the 1 - client_dropout prior.
            raise ValueError(
                "--sampler throughput / --deadline_quantile require "
                "telemetry (drop --no_telemetry: the session feeds "
                "the throughput measurements these policies read)")
        if self.plan_transport not in ("", "collective", "emulated"):
            raise ValueError(
                f"unknown plan_transport {self.plan_transport!r} "
                "(choices: '' — none, collective — the production "
                "one-to-all host collective, emulated — the in-process "
                "N-controller harness; parallel/plantransport.py)")
        if self.plan_controllers < 1:
            raise ValueError("plan_controllers must be >= 1")
        if self.plan_transport == "emulated" and self.plan_controllers < 2:
            raise ValueError(
                "--plan_transport emulated needs --plan_controllers "
                ">= 2 (one coordinator plus at least one follower — "
                "a single controller has nobody to broadcast to and "
                "would silently test nothing)")
        if self.plan_transport and self.do_checkpoint \
                and not self.journal_path:
            raise ValueError(
                "--plan_transport with --checkpoint requires an "
                "explicit --journal_path: the write-ahead plan "
                "journal is the authoritative decision log a "
                "--resume takeover replays, and the default journal "
                "location (<run dir>/journal.jsonl) is a fresh "
                "timestamped directory each run — a resumed process "
                "could never find the crashed run's stream and would "
                "silently recompute (and diverge from) its durably "
                "committed plans")
        if self.plan_transport == "emulated" and self.multihost:
            raise ValueError(
                "--plan_transport emulated is the IN-PROCESS "
                "N-controller harness (one process pretending to be "
                "many) and cannot coexist with real multihost; use "
                "--plan_transport collective there")
        if (self.multihost and not self.plan_transport
                and (self.sampler != "uniform"
                     or self.deadline_quantile > 0
                     or self.target_survivors > 0)):
            raise ValueError(
                "scheduler policies (--sampler throughput / "
                "--deadline_quantile / --target_survivors) derive from "
                "process-local wall-clock throughput measurements and "
                "would diverge across controllers without a plan "
                "transport: attach --plan_transport collective (the "
                "coordinator broadcasts each round's RoundPlan and "
                "every process installs the received plan — "
                "parallel/plantransport.py)")
        if self.async_admit_rounds < 0:
            raise ValueError(
                "async_admit_rounds must be >= 0 (0 = synchronous "
                "stragglers, k = admit late contributions k rounds on)")
        if not 0.0 < self.async_staleness_decay <= 1.0:
            raise ValueError(
                f"async_staleness_decay={self.async_staleness_decay} "
                "must be in (0, 1] (1.0 = undiscounted late admission)")
        if self.multihost and self.pipeline:
            raise ValueError(
                "--pipeline is single-controller only for now: the "
                "persistence writer threads and the one-span-late "
                "commit would need cross-process barriers (a ROADMAP "
                "opening — the plan transport does not cover it)")
        if (self.multihost and self.async_admit_rounds > 0
                and not self.plan_transport):
            raise ValueError(
                "--async_admit_rounds needs a plan transport in "
                "multihost runs: the defer/admit merges are control "
                "decisions every controller must prove identical "
                "(each process defers/admits its OWN batch rows, but "
                "the slot/weight stream is digest-cross-checked) — "
                "attach --plan_transport collective "
                "(parallel/plantransport.py)")
        if self.speed_match:
            if self.async_admit_rounds <= 0:
                raise ValueError(
                    "--speed_match defers measured-slow clients into "
                    "async admission slots — it needs "
                    "--async_admit_rounds > 0 to have somewhere to "
                    "put them")
            if not 0.0 < self.speed_match_target < 1.0:
                raise ValueError(
                    f"speed_match_target={self.speed_match_target} "
                    "must be in (0, 1) (the deferred cohort fraction "
                    "the ratio is steered toward)")
            if self.speed_match_step <= 0:
                raise ValueError(
                    "speed_match_step must be > 0 (the multiplicative "
                    "adjustment per observed round)")
            if not (0.0 < self.speed_ratio_min
                    <= self.speed_ratio_max < 1.0):
                raise ValueError(
                    f"need 0 < speed_ratio_min={self.speed_ratio_min} "
                    f"<= speed_ratio_max={self.speed_ratio_max} < 1: "
                    "a ratio >= 1 would flag at-median clients as "
                    "slow and could defer half the cohort every round")
        if self.scan_span_palette.strip():
            pal = self.span_palette
            if any(p <= 0 for p in pal):
                raise ValueError(
                    f"scan_span_palette={self.scan_span_palette!r}: "
                    "span lengths must be positive")
            if 1 not in pal:
                raise ValueError(
                    f"scan_span_palette={self.scan_span_palette!r} "
                    "must include 1: the stream tail decomposes "
                    "greedily over the palette, and only a 1-span can "
                    "finish an arbitrary leftover without tracing a "
                    "new program shape")
            if not self.scan_rounds:
                raise ValueError(
                    "--scan_span_palette sizes the scanned staging "
                    "loop — enable --scan_rounds")
            if self.scan_span > 0:
                raise ValueError(
                    "--scan_span and --scan_span_palette are mutually "
                    "exclusive: the palette controller owns the span "
                    "length (static spans = --scan_span alone)")
        if self.adapt_staleness:
            if self.async_admit_rounds <= 0:
                raise ValueError(
                    "--adapt_staleness tunes the async admission "
                    "staleness discount — it needs "
                    "--async_admit_rounds > 0 for the discount to "
                    "apply to anything")
            if self.staleness_step <= 0:
                raise ValueError(
                    "staleness_step must be > 0 (the multiplicative "
                    "adjustment per observed round)")
            if not (0.0 < self.staleness_decay_min
                    <= self.staleness_decay_max <= 1.0):
                raise ValueError(
                    f"need 0 < staleness_decay_min="
                    f"{self.staleness_decay_min} <= staleness_decay_max="
                    f"{self.staleness_decay_max} <= 1 (1.0 = "
                    "undiscounted late admission)")
            if (self.pipeline and self.scan_rounds
                    and self.scan_span <= 0
                    and not self.scan_span_palette.strip()):
                raise ValueError(
                    "--adapt_staleness stamps a fixed-lag decay (the "
                    "lag bounds how far staging can run ahead of "
                    "commits), so pipelined --scan_rounds needs a "
                    "bounded span: set --scan_span or "
                    "--scan_span_palette (epoch-sized spans have no "
                    "static bound)")
        if self.writer_drain_timeout_s < 0:
            raise ValueError(
                "writer_drain_timeout_s must be >= 0 (0 = wait "
                "forever; positive = a hung journal/checkpoint/spill "
                "writer drain raises TimeoutError naming the writer)")
        if self.state_tier not in ("device", "host"):
            raise ValueError(
                f"unknown state_tier {self.state_tier!r} (choices: "
                "device — full population in device HBM, the default — "
                "or host — LRU working set on device, cold tail on "
                "host; federated/statestore.py)")
        if self.state_working_set < 0:
            raise ValueError("state_working_set must be >= 0")
        if self.state_tier != "device":
            if self.state_working_set <= 0:
                raise ValueError(
                    "--state_tier host requires --state_working_set N "
                    "(the device-HBM row budget; must be >= "
                    "num_workers)")
            if self.state_working_set < self.num_workers:
                raise ValueError(
                    f"state_working_set={self.state_working_set} < "
                    f"num_workers={self.num_workers}: one round's "
                    "whole cohort must fit in the device working set")
            if self.multihost:
                raise ValueError(
                    "--state_tier host is single-controller only for "
                    "now: the host tail is process-local state and "
                    "would need per-process sharded spill/restore "
                    "(the coordinator-broadcast ROADMAP opening)")
        if self.state_spill_dir and self.state_tier == "device":
            raise ValueError(
                "--state_spill_dir backs the HOST tail and requires "
                "--state_tier host (the device tier has no tail to "
                "spill)")
        if self.state_working_set > 0 and self.state_tier == "device":
            # fail loud rather than silently allocating the full
            # [padded_population, D] blocks in HBM — the exact OOM
            # the flag was set to prevent
            raise ValueError(
                "--state_working_set caps the device-resident rows of "
                "the HOST tier and requires --state_tier host (the "
                "device tier keeps every row in HBM, uncapped)")
        if self.sketch_table_dtype not in ("f32", "bf16", "int8"):
            raise ValueError(
                f"unknown sketch_table_dtype {self.sketch_table_dtype!r} "
                "(choices: f32, bf16, int8)")
        if self.sketch_table_dtype != "f32" and self.mode != "sketch":
            # fail loud rather than silently transmitting f32: the flag
            # names the SKETCH table, and no other mode has one
            raise ValueError(
                "--sketch_table_dtype quantizes the transmitted sketch "
                f"table and requires --mode sketch (got {self.mode!r})")
        if self.down_k < 0:
            raise ValueError("down_k must be >= 0 (0 = share the upload k)")
        if self.down_k > self.grad_size > 0:
            raise ValueError(
                f"down_k={self.down_k} exceeds grad_size={self.grad_size}")
        if self.dp_noise_mult != 0 and self.mode != "dp_sketch":
            # fail loud rather than silently training noise-free: the
            # flag names the dp_sketch Gaussian mechanism
            raise ValueError(
                "--dp_noise_mult calibrates the dp_sketch Gaussian "
                f"mechanism and requires --mode dp_sketch (got "
                f"{self.mode!r}; --dp/--noise_multiplier is the "
                "separate per-gradient DP path)")
        if self.dp_target_epsilon != 0 and self.mode != "dp_sketch":
            raise ValueError(
                "--dp_target_epsilon bounds the dp_sketch privacy "
                "budget and requires --mode dp_sketch (got "
                f"{self.mode!r})")
        # plugin-specific invariants (ISSUE 19): each Compressor
        # rejects the config combinations it does not compose with
        self.compressor.validate(self)
        return self


def _build_parser(default_lr: Optional[float] = None) -> argparse.ArgumentParser:
    """The reference CLI surface, flag for flag (utils.py:102-230)."""
    p = argparse.ArgumentParser()
    p.add_argument("--test", action="store_true", dest="do_test")
    p.add_argument("--mode", choices=list(MODES), default="sketch")
    p.add_argument("--tensorboard", dest="use_tensorboard", action="store_true")
    p.add_argument("--seed", type=int, default=21)

    p.add_argument("--model", default="ResNet9")
    p.add_argument("--finetune", action="store_true", dest="do_finetune")
    p.add_argument("--checkpoint", action="store_true", dest="do_checkpoint")
    p.add_argument("--checkpoint_path", type=str, default="./checkpoint")
    p.add_argument("--checkpoint_every", type=int, default=0)
    p.add_argument("--resume", action="store_true")
    p.add_argument("--finetune_path", type=str, default="./finetune")
    p.add_argument("--finetuned_from", type=str, choices=list(FED_DATASETS))
    p.add_argument("--num_results_train", type=int, default=2)
    p.add_argument("--num_results_val", type=int, default=2)
    p.add_argument("--dataset_name", type=str, default="CIFAR10",
                   choices=list(FED_DATASETS))
    p.add_argument("--dataset_dir", type=str, default="./dataset")
    p.add_argument("--batchnorm", action="store_true", dest="do_batchnorm")
    p.add_argument("--nan_threshold", type=float, default=999)
    p.add_argument("--profile", action="store_true", dest="do_profile",
                   help="jax.profiler trace of the first epoch")
    p.add_argument("--no_telemetry", action="store_false",
                   dest="telemetry",
                   help="disable on-device round telemetry + the run "
                        "journal (telemetry is ON by default and "
                        "bit-neutral to training; see README "
                        "'Observability')")
    p.add_argument("--journal_path", type=str, default="",
                   help="structured JSONL run-journal path (default: "
                        "<run dir>/journal.jsonl; "
                        "telemetry/journal.py)")
    p.add_argument("--profile_spans", type=str, default="",
                   help="with --scan_rounds: jax.profiler-capture "
                        "scanned span indices [A, B), e.g. '2:4' "
                        "(trace lands in <run dir>/profile_spans and "
                        "the capture is journaled)")
    p.add_argument("--trace", action="store_true",
                   help="graftscope round-lifecycle tracing: "
                        "monotonic stage spans (plan/stage/dispatch/"
                        "device_execute/collect/tier motion/writer "
                        "queue-wait+fsync) buffered per thread and "
                        "flushed as batched `trace` journal events; "
                        "export with scripts/trace_export.py "
                        "(Perfetto), analyze with journal_summary.py "
                        "(per-stage p50/p95, overlap efficiency). "
                        "OFF by default — zero overhead, journal "
                        "unchanged (telemetry/trace.py)")
    p.add_argument("--debug_transfer_guard", action="store_true",
                   help="arm jax.transfer_guard('disallow') around "
                        "the steady-state training loop: any implicit "
                        "host<->device transfer (a hidden per-round "
                        "sync) raises instead of silently stalling "
                        "rounds (analysis/runtime.forbid_transfers)")

    p.add_argument("--k", type=int, default=50000)
    p.add_argument("--num_cols", type=int, default=500000)
    p.add_argument("--num_rows", type=int, default=5)
    p.add_argument("--num_blocks", type=int, default=20)
    p.add_argument("--topk_down", action="store_true", dest="do_topk_down")
    p.add_argument("--down_k", type=int, default=0,
                   help="download top-k budget (0 = share --k); see "
                        "Config.down_k")
    p.add_argument("--sketch_table_dtype",
                   choices=("f32", "bf16", "int8"), default="f32",
                   help="wire dtype of the transmitted sketch table "
                        "(sketch mode): bf16/int8 quantize the client-"
                        "sum table before aggregation — error feedback "
                        "absorbs the rounding noise, the accountant "
                        "bills bytes at this element size")

    p.add_argument("--local_momentum", type=float, default=0.9)
    p.add_argument("--virtual_momentum", type=float, default=0)
    p.add_argument("--weight_decay", type=float, default=5e-4)
    p.add_argument("--num_epochs", type=float, default=24)
    p.add_argument("--num_fedavg_epochs", type=int, default=1)
    p.add_argument("--fedavg_batch_size", type=int, default=-1)
    p.add_argument("--fedavg_lr_decay", type=float, default=1)
    p.add_argument("--error_type", choices=list(ERROR_TYPES), default="none")
    p.add_argument("--lr_scale", type=float, default=default_lr)
    p.add_argument("--pivot_epoch", type=float, default=5)

    p.add_argument("--client_dropout", type=float, default=0.0,
                   help="per-round probability a sampled client fails "
                        "to complete the round (survivor-reweighted "
                        "aggregation; Config.client_dropout)")
    p.add_argument("--no_donate_round_state", action="store_false",
                   dest="donate_round_state",
                   help="disable buffer donation of dead-after-"
                        "dispatch round state (donation is ON by "
                        "default: in-place HBM reuse of the server/"
                        "client state blocks, bit-identical results; "
                        "disable for callers that re-dispatch from a "
                        "retained state object or need failed span "
                        "dispatches to stay retryable — "
                        "Config.donate_round_state)")
    p.add_argument("--straggler_rate", type=float, default=0.0,
                   help="per-round probability a sampled client is a "
                        "straggler completing only a fraction of its "
                        "local work (Config.straggler_rate)")
    p.add_argument("--straggler_min_work", type=float, default=0.1,
                   help="lower bound of a straggler's uniform work-"
                        "fraction draw (Config.straggler_min_work)")
    p.add_argument("--straggler_cutoff", type=float, default=0.0,
                   help="work fractions below this degrade to client "
                        "dropout: no upload, state bit-untouched "
                        "(Config.straggler_cutoff)")
    p.add_argument("--update_screen", choices=list(SCREEN_MODES),
                   default="off",
                   help="in-round update admission (ISSUE 16, "
                        "federated/round.py): finite screens NaN/Inf "
                        "client updates, norm additionally screens "
                        "cohort-median norm outliers; a screened "
                        "client takes exactly the dropped-client path "
                        "(off = default, bit-identical programs)")
    p.add_argument("--screen_norm_mult", type=float, default=5.0,
                   help="norm-screen outlier threshold: screen a "
                        "client whose update l2 exceeds this multiple "
                        "of the cohort median l2 "
                        "(Config.screen_norm_mult)")
    p.add_argument("--poison_rate", type=float, default=0.0,
                   help="value-fault injection: per-round probability "
                        "a sampled client's update is corrupted "
                        "(deterministic in seed+round on its own PRNG "
                        "domain; utils/faults.poison_mask)")
    p.add_argument("--poison_kind", choices=list(POISON_KINDS),
                   default="nan",
                   help="corruption applied to a poisoned client's "
                        "update: nan/inf overwrite it, scale "
                        "multiplies by 2^40 (finite explosion — only "
                        "the norm screen catches it)")
    p.add_argument("--aggregator", choices=list(AGGREGATORS),
                   default="mean",
                   help="cross-client reduction inside the jitted "
                        "round (ISSUE 17): mean (default, reference "
                        "FetchSGD sum), coord_median / trimmed_mean "
                        "(per-cell order statistics over admitted "
                        "client tables), norm_clip (clip each client "
                        "to the cohort median l2 before the weighted "
                        "mean)")
    p.add_argument("--trim_beta", type=float, default=0.2,
                   help="trimmed_mean: fraction of admitted clients "
                        "trimmed from EACH end of every cell's order "
                        "statistics (Config.trim_beta)")
    p.add_argument("--byzantine_rate", type=float, default=0.0,
                   help="scripted adversary harness: per-round "
                        "probability a sampled client is an attacker "
                        "(deterministic in seed+round on its own "
                        "'byzantine' PRNG domain; "
                        "utils/faults.byzantine_mask)")
    p.add_argument("--attack", choices=list(ATTACKS),
                   default="sign_flip",
                   help="crafted update an attacker submits: "
                        "sign_flip/scaled are local corruptions; "
                        "colluding and little_is_enough are "
                        "coordinated, finite, norm-plausible updates "
                        "built from the honest cohort's statistics — "
                        "the class admission screening cannot catch")
    p.add_argument("--target_screened_rate", type=float, default=-1.0,
                   help="adaptive screening: adjust the norm-screen "
                        "threshold toward this per-round screened "
                        "fraction, every adjustment riding the "
                        "journaled RoundPlan (negative = off, static "
                        "--screen_norm_mult; requires --update_screen "
                        "norm; scheduler.AdaptiveScreenController)")
    p.add_argument("--screen_adapt_step", type=float, default=0.5,
                   help="adaptive screening multiplicative step: an "
                        "adjustment scales the threshold by "
                        "(1 + step) up or down "
                        "(Config.screen_adapt_step)")
    p.add_argument("--screen_mult_min", type=float, default=1.5,
                   help="adaptive screening threshold floor "
                        "(Config.screen_mult_min)")
    p.add_argument("--screen_mult_max", type=float, default=64.0,
                   help="adaptive screening threshold ceiling "
                        "(Config.screen_mult_max)")
    p.add_argument("--speed_match", action="store_true",
                   help="cohort speed-matching controller "
                        "(control/speed.py): defer clients measured "
                        "slower than speed_ratio x cohort-median rate "
                        "into --async_admit_rounds slots, the ratio "
                        "self-tuning toward --speed_match_target "
                        "(requires --async_admit_rounds > 0)")
    p.add_argument("--speed_match_target", type=float, default=0.25,
                   help="deferred cohort fraction the speed-matching "
                        "ratio is steered toward "
                        "(Config.speed_match_target)")
    p.add_argument("--speed_match_step", type=float, default=0.25,
                   help="speed-matching multiplicative step per "
                        "observed round (Config.speed_match_step)")
    p.add_argument("--speed_ratio", type=float, default=0.5,
                   help="starting slow-client threshold as a fraction "
                        "of the cohort median rate "
                        "(Config.speed_ratio)")
    p.add_argument("--speed_ratio_min", type=float, default=0.25,
                   help="speed-matching ratio floor "
                        "(Config.speed_ratio_min)")
    p.add_argument("--speed_ratio_max", type=float, default=0.9,
                   help="speed-matching ratio ceiling; must stay < 1 "
                        "(Config.speed_ratio_max)")
    p.add_argument("--scan_span_palette", type=str, default="",
                   help="adaptive span cadence (control/span.py): "
                        "comma-separated span lengths the scanned "
                        "staging loop may flush at, e.g. 1,2,4 — each "
                        "traces once at warmup, the seconds-per-round "
                        "EMA picks the steady-state length; must "
                        "include 1; empty = static --scan_span "
                        "(Config.scan_span_palette)")
    p.add_argument("--adapt_staleness", action="store_true",
                   help="adaptive staleness decay "
                        "(control/staleness.py): drive "
                        "async_staleness_decay from the "
                        "estimate_residual metric between the "
                        "configured bounds (requires "
                        "--async_admit_rounds > 0)")
    p.add_argument("--staleness_target", type=float, default=0.3,
                   help="estimate_residual level above which late "
                        "admissions are discounted harder "
                        "(Config.staleness_target)")
    p.add_argument("--staleness_step", type=float, default=0.25,
                   help="staleness-decay multiplicative step per "
                        "observed round (Config.staleness_step)")
    p.add_argument("--staleness_decay_min", type=float, default=0.2,
                   help="adaptive staleness decay floor "
                        "(Config.staleness_decay_min)")
    p.add_argument("--staleness_decay_max", type=float, default=0.95,
                   help="adaptive staleness decay ceiling "
                        "(Config.staleness_decay_max)")
    p.add_argument("--rollback_screen_rounds", type=int, default=8,
                   help="after a numeric_trip rollback, force update "
                        "screening on for this many rounds so the "
                        "replayed fault is screened instead of "
                        "re-tripping (Config.rollback_screen_rounds)")
    p.add_argument("--max_numeric_rollbacks", type=int, default=2,
                   help="cap on numeric_trip rollbacks per run; past "
                        "it the driver fails loud instead of "
                        "thrashing (Config.max_numeric_rollbacks)")
    p.add_argument("--keep_checkpoints", type=int, default=3,
                   help="keep the newest k rotated mid-run checkpoints "
                        "(utils/checkpoint.save_rotating)")
    p.add_argument("--ckpt_max_age_hours", type=float, default=0.0,
                   help="also prune rotated checkpoints older than "
                        "this wall-clock age in hours; 0 disables "
                        "(utils/checkpoint.save_rotating)")
    p.add_argument("--ckpt_every_spans", type=int, default=1,
                   help="with --scan_rounds and --checkpoint_every: "
                        "save at every k-th span boundary (1 bounds a "
                        "mid-span preemption's loss to one span; each "
                        "save is a full state gather — raise k to "
                        "bound the save rate; 0 = epoch cadence only)")

    p.add_argument("--pipeline", action="store_true",
                   help="pipelined round engine: double-buffered "
                        "scanned dispatch (span t+1 stages while span "
                        "t runs on device) + journal/checkpoint "
                        "persistence on bounded-queue writer threads. "
                        "OFF by default — the default loop is bit-"
                        "identical to the pre-feature program "
                        "(Config.pipeline)")
    p.add_argument("--async_admit_rounds", type=int, default=0,
                   help="buffered async aggregation: defer a "
                        "straggler's contribution out of its round "
                        "(bit-exactly the dropped-client path) and "
                        "admit it k rounds later with a staleness-"
                        "discounted work fraction on the existing "
                        "straggler operand (0 = synchronous; "
                        "Config.async_admit_rounds)")
    p.add_argument("--async_staleness_decay", type=float, default=0.5,
                   help="per-round decay of a late-admitted "
                        "contribution's work fraction: weight = "
                        "decay**rounds_late (1.0 = undiscounted)")
    p.add_argument("--state_tier", choices=("device", "host"),
                   default="device",
                   help="client-state residency tier: device (full "
                        "population sharded in device HBM, the "
                        "default — bit-identical to the pre-feature "
                        "program) or host (LRU working set of "
                        "--state_working_set rows on device, cold "
                        "tail spilled to host off the critical path; "
                        "federated/statestore.py)")
    p.add_argument("--state_working_set", type=int, default=0,
                   help="with --state_tier host: device-HBM working-"
                        "set size in client rows (>= num_workers; "
                        "on the scanned path >= a span's distinct "
                        "clients)")
    p.add_argument("--state_spill_dir", type=str, default="",
                   help="with --state_tier host: disk-back the host "
                        "tail with sparse f32 memmaps under this "
                        "directory (scratch state, rebuilt from "
                        "crows_* checkpoints on resume)")
    p.add_argument("--plan_transport",
                   choices=("", "collective", "emulated"), default="",
                   help="coordinator-broadcast control plane (ISSUE "
                        "12, parallel/plantransport.py): collective = "
                        "the production one-to-all host collective "
                        "(lifts the single-controller rejection of "
                        "non-default schedulers / --async_admit_rounds "
                        "in multihost runs), emulated = the in-process "
                        "N-controller harness (--plan_controllers; "
                        "chaos scripting via CCTPU_EMU_COORD_CRASH / "
                        "CCTPU_EMU_COORDINATOR env vars), '' = none "
                        "(the default — bit-identical to the "
                        "transport-free build)")
    p.add_argument("--plan_controllers", type=int, default=2,
                   help="controller count of the emulated plan-"
                        "transport harness (>= 2 when --plan_transport "
                        "emulated)")
    p.add_argument("--writer_drain_timeout_s", type=float, default=0.0,
                   help="flush/drain timeout for the bounded-queue "
                        "writer threads (journal, checkpoint, state "
                        "spill): a hung fsync raises TimeoutError "
                        "naming the stuck writer instead of hanging "
                        "the crash-time drain (0 = wait forever)")
    p.add_argument("--sampler", choices=("uniform", "throughput"),
                   default="uniform",
                   help="participant-sampling policy: uniform (bit-"
                        "identical to the pre-scheduler draw) or "
                        "throughput (deprioritize measured-slow "
                        "clients; commefficient_tpu/scheduler)")
    p.add_argument("--explore_floor", type=float, default=0.1,
                   help="throughput sampler's exploration floor: every "
                        "alive client keeps >= floor/num_alive "
                        "selection probability per slot")
    p.add_argument("--deadline_quantile", type=float, default=0.0,
                   help="per-round wall-clock deadline as this "
                        "quantile of participants' measured time "
                        "estimates; slower participants get truncated "
                        "work fractions on the straggler operand "
                        "(0 = no deadline)")
    p.add_argument("--deadline_min_work", type=float, default=0.1,
                   help="floor of a deadline-truncated work fraction "
                        "(fractions below --straggler_cutoff still "
                        "degrade to dropout)")
    p.add_argument("--target_survivors", type=int, default=0,
                   help="over-provision sampling so expected round "
                        "survivors hit this count; surplus slots ride "
                        "as survivor-mask zeros (0 = fill all slots)")
    p.add_argument("--port", type=int, default=5315)
    p.add_argument("--num_clients", type=int)
    p.add_argument("--num_workers", type=int, default=1)
    p.add_argument("--max_local_batch", type=int, default=-1,
                   help="cap the static per-client batch dim when "
                        "local_batch_size=-1 (bounds device staging "
                        "memory at ImageNet scale)")
    p.add_argument("--device", type=str, default="tpu")
    p.add_argument("--num_devices", type=int, default=1)
    p.add_argument("--share_ps_gpu", action="store_true")
    p.add_argument("--scan_rounds", action="store_true",
                   help="run each epoch as one scanned device program")
    p.add_argument("--scan_span", type=int, default=0,
                   help="flush scanned rounds every N rounds (0=epoch)")
    p.add_argument("--model_parallel", type=int, default=1,
                   help="tensor-parallel degree over the mesh's model "
                        "axis (GPT2-scale models; parallel/tp.py)")
    p.add_argument("--num_slices", type=int, default=1,
                   help="slice-major clients layout over DCN "
                        "(emulated when devices report no slice "
                        "topology; parallel/mesh.py)")
    p.add_argument("--multihost", action="store_true",
                   help="multi-controller run: jax.distributed."
                        "initialize before any backend use (auto-"
                        "detected grid on TPU pods; explicit "
                        "--coordinator_address/--num_processes/"
                        "--process_id elsewhere)")
    p.add_argument("--coordinator_address", type=str, default="",
                   help="host:port of process 0's coordination service")
    p.add_argument("--num_processes", type=int, default=0,
                   help="total controller processes (0 = auto-detect)")
    p.add_argument("--process_id", type=int, default=-1,
                   help="this process's index (-1 = auto-detect)")
    p.add_argument("--bf16", action="store_true", dest="do_bf16",
                   help="bfloat16 client fwd/bwd (f32 master weights)")
    p.add_argument("--remat", action="store_true", dest="do_remat",
                   help="rematerialize GPT2 blocks on backward "
                        "(activation memory -> O(1) blocks)")
    p.add_argument("--iid", action="store_true", dest="do_iid")
    p.add_argument("--train_dataloader_workers", type=int, default=0)
    p.add_argument("--val_dataloader_workers", type=int, default=0)

    p.add_argument("--model_checkpoint", type=str, default="gpt2")
    p.add_argument("--num_candidates", type=int, default=2)
    p.add_argument("--max_history", type=int, default=2)
    p.add_argument("--local_batch_size", type=int, default=8)
    p.add_argument("--valid_batch_size", type=int, default=8)
    p.add_argument("--microbatch_size", type=int, default=-1)
    p.add_argument("--lm_coef", type=float, default=1.0)
    p.add_argument("--mc_coef", type=float, default=1.0)
    p.add_argument("--max_grad_norm", type=float)
    p.add_argument("--personality_permutations", type=int, default=1)
    p.add_argument("--eval_before_start", action="store_true")

    p.add_argument("--dp", action="store_true", dest="do_dp")
    p.add_argument("--dp_mode", choices=list(DP_MODES), default="worker")
    p.add_argument("--l2_norm_clip", type=float, default=1.0)
    p.add_argument("--noise_multiplier", type=float, default=0.0)

    p.add_argument("--powersgd_rank", type=int, default=2,
                   help="with --mode powersgd: rank of the per-client "
                        "P/Q power-iteration factors — the wire "
                        "carries (m+n)*rank floats per client "
                        "(compress/powersgd.py)")
    p.add_argument("--dp_clip", type=float, default=1.0,
                   help="with --mode dp_sketch: per-client Frobenius "
                        "clip of the count-scaled sketch table — the "
                        "sum query's l2 sensitivity bound "
                        "(compress/dp_sketch.py)")
    p.add_argument("--dp_noise_mult", type=float, default=0.0,
                   help="with --mode dp_sketch: Gaussian noise "
                        "multiplier — noise std dp_noise_mult*dp_clip "
                        "added once per round to the aggregated table "
                        "inside the jitted round")
    p.add_argument("--dp_target_epsilon", type=float, default=0.0,
                   help="with --mode dp_sketch: fail-loud privacy "
                        "budget ceiling at --dp_delta; the Rényi "
                        "accountant journals cumulative epsilon per "
                        "round as `privacy` events and the run raises "
                        "when the budget is exhausted (0 = track but "
                        "never fail)")
    p.add_argument("--dp_delta", type=float, default=1e-5,
                   help="with --mode dp_sketch: the delta of the "
                        "(epsilon, delta)-DP guarantee the accountant "
                        "reports")
    return p


def parse_args(default_lr: Optional[float] = None, argv=None) -> Config:
    ns = _build_parser(default_lr).parse_args(argv)
    cfg = Config(**vars(ns))
    return cfg.validate()

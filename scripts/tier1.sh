#!/usr/bin/env bash
# The repo's tier-1 verify recipe, exactly as ROADMAP.md specifies it —
# committed so the command is code, not tribal knowledge. Run from the
# repo root:
#
#   bash scripts/tier1.sh
#
# Exit code is pytest's; the DOTS_PASSED line is the driver's pass
# counter (count of '.' progress dots in the captured log).
set -o pipefail
# trace-safety lint first (fast, pure-ast, no device): a GL violation
# fails tier-1 before any test runs — its log stays out of the pytest
# capture below so DOTS_PASSED counting is unaffected
bash "$(dirname "$0")/lint.sh" || { echo "GRAFTLINT_FAILED"; exit 1; }
# program audit second (ISSUE 7): trace the round programs and check
# forbidden primitives / population scaling / donation / the static
# cost baseline. Its audit_digest is journaled and the journal must
# validate, so the digest record format is exercised every CI run.
AJR=/tmp/_t1_audit.jsonl
rm -f "$AJR"
timeout -k 10 300 bash "$(dirname "$0")/audit.sh" --journal "$AJR" \
    || { echo "GRAFTAUDIT_FAILED"; exit 1; }
python scripts/journal_summary.py "$AJR" \
    || { echo "AUDIT_JOURNAL_INVALID"; exit 1; }
# mesh audit third (ISSUE 8): trace the round programs + scanned span
# under the simulated 8-device meshes (1-D clients, 2-D clients x
# model, emulated 2-slice) and check the sharding/collective contracts
# (AU007-AU011) plus the per-link ICI/DCN byte report against
# meshaudit.baseline.json. Exit 1 = contract violation, 2 = baseline
# drift; either fails tier-1. Its mesh_audit_digest is journaled and
# the journal must validate.
MJR=/tmp/_t1_meshaudit.jsonl
rm -f "$MJR"
timeout -k 10 300 env \
    XLA_FLAGS="--xla_force_host_platform_device_count=8" \
    bash "$(dirname "$0")/audit.sh" --mesh --journal "$MJR" \
    || { echo "GRAFTMESH_FAILED"; exit 1; }
python scripts/journal_summary.py "$MJR" \
    || { echo "MESH_JOURNAL_INVALID"; exit 1; }
# concurrency audit fourth (ISSUE 14): graftsync — pure-AST over the
# host control plane's five packages, checking the shared-state guard
# registry, the static lock-order graph, queue-ownership transfer,
# blocking-under-lock, thread lifecycle, and the durability-ordering
# edges (rules SY001-SY006; empty exact-match baseline). Exit 1 =
# contract violation, 2 = baseline drift; either fails tier-1. Its
# sync_audit_digest is journaled and the journal must validate, so
# the digest record format is exercised every CI run.
SYJR=/tmp/_t1_syncaudit.jsonl
rm -f "$SYJR"
timeout -k 10 120 bash "$(dirname "$0")/sync.sh" --journal "$SYJR" \
    || { echo "GRAFTSYNC_FAILED"; exit 1; }
python scripts/journal_summary.py "$SYJR" \
    || { echo "SYNC_JOURNAL_INVALID"; exit 1; }
# numerics audit fifth (ISSUE 18): graftnum — walk every registered
# program's ClosedJaxpr with the dtype/finiteness dataflow lattice and
# check NaN-unsafe mask arithmetic, the PRECISION_SEAMS downcast
# registry, zero-guarded denominators, and replay-determinism (rules
# NU001-NU005; empty exact-match baseline), plus the per-program
# worst-case reassociation ulp bound. Exit 1 = contract violation,
# 2 = baseline drift; either fails tier-1. Its num_audit_digest is
# journaled and the journal must validate, so the digest record format
# is exercised every CI run.
NJR=/tmp/_t1_numaudit.jsonl
rm -f "$NJR"
timeout -k 10 300 bash "$(dirname "$0")/num.sh" --journal "$NJR" \
    || { echo "GRAFTNUM_FAILED"; exit 1; }
python scripts/journal_summary.py "$NJR" \
    || { echo "NUM_JOURNAL_INVALID"; exit 1; }
rm -f /tmp/_t1.log
timeout -k 10 870 env JAX_PLATFORMS=cpu python -m pytest tests/ -q \
    -m 'not slow' --continue-on-collection-errors -p no:cacheprovider \
    -p no:xdist -p no:randomly 2>&1 | tee /tmp/_t1.log
rc=${PIPESTATUS[0]}
echo DOTS_PASSED=$(grep -aE '^[.FEsx]+( *\[ *[0-9]+%\])?$' /tmp/_t1.log | tr -cd . | wc -c)

# telemetry smoke + journal invariant check (ISSUE 4 satellite): a
# tiny scanned driver run with the journal and the steady-state
# transfer guard armed, then scripts/journal_summary.py over the
# journal it wrote — malformed or duplicate-round events fail tier-1.
# Only runs when the pytest gate above already passed.
if [ "$rc" -eq 0 ]; then
  # lock-order-sanitized concurrency suites (ISSUE 14): the pipeline /
  # statetier / controlplane markers — the writer-thread-richest
  # suites in the tree — re-run with graftsync's runtime twin armed
  # (CCTPU_SYNC_SANITIZE=1, tests/conftest.py): threading.Lock/RLock
  # are swapped for recording proxies, the observed acquisition graph
  # must stay acyclic per test, and queue handoffs get deterministic
  # interleaving delays that widen producer/drain race windows. A
  # lock-order cycle or a stress-exposed writer race fails tier-1.
  rm -f /tmp/_t1_sync.log
  timeout -k 10 600 env JAX_PLATFORMS=cpu CCTPU_SYNC_SANITIZE=1 \
      python -m pytest tests/ -q \
      -m 'pipeline or statetier or controlplane' \
      -p no:cacheprovider -p no:xdist -p no:randomly \
      > /tmp/_t1_sync.log 2>&1 \
      || { echo "SYNC_SANITIZED_SUITES_FAILED"; \
           tail -60 /tmp/_t1_sync.log; exit 1; }

  # numeric-sanitized value-fault suites (ISSUE 18): the valuefaults /
  # byzantine markers — the suites that deliberately push poison and
  # adversarial updates through the round — re-run with graftnum's
  # runtime twin armed (CCTPU_NUM_SANITIZE=1, tests/conftest.py): every
  # exported round-metric vector passes a post-dispatch finite guard,
  # so a NaN/inf that screening or robust aggregation should have
  # absorbed but instead leaked into telemetry fails tier-1 with the
  # offending metric named.
  rm -f /tmp/_t1_num.log
  timeout -k 10 600 env JAX_PLATFORMS=cpu CCTPU_NUM_SANITIZE=1 \
      python -m pytest tests/ -q \
      -m 'valuefaults or byzantine' \
      -p no:cacheprovider -p no:xdist -p no:randomly \
      > /tmp/_t1_num.log 2>&1 \
      || { echo "NUM_SANITIZED_SUITES_FAILED"; \
           tail -60 /tmp/_t1_num.log; exit 1; }

  JR=/tmp/_t1_journal.jsonl
  rm -f "$JR"
  timeout -k 10 300 env JAX_PLATFORMS=cpu \
      XLA_FLAGS="--xla_force_host_platform_device_count=8" \
      python -m commefficient_tpu.training.cv_train \
      --test --dataset_name CIFAR10 --mode uncompressed \
      --local_momentum 0.0 --num_workers 8 --local_batch_size 8 \
      --num_epochs 0.05 --valid_batch_size 16 --lr_scale 0.1 \
      --scan_rounds --scan_span 1 --debug_transfer_guard \
      --journal_path "$JR" --dataset_dir /tmp/_t1_ds >/dev/null 2>&1 \
      || { echo "TELEMETRY_SMOKE_FAILED"; exit 1; }
  python scripts/journal_summary.py "$JR" \
      || { echo "JOURNAL_INVALID"; exit 1; }

  # recompile regression gate (ISSUE 13 satellite): after
  # mark_steady_state every backend compile journals as a
  # compile_warning — a silent retrace in the steady-state loop is a
  # TPU performance cliff, so any such event in a driver smoke's
  # journal fails tier-1 (eval-phase compiles run under
  # expect_compiles and are exempt by construction).
  check_no_recompiles() {
    python - "$1" <<'PYEOF'
import json, sys
warns = [json.loads(l) for l in open(sys.argv[1])
         if '"compile_warning"' in l]
warns = [w for w in warns if w.get("event") == "compile_warning"]
assert not warns, (
    f"{len(warns)} steady-state recompile(s) journaled in "
    f"{sys.argv[1]}: " + "; ".join(
        str(w.get("what", "?")) for w in warns[:5]))
PYEOF
  }
  check_no_recompiles "$JR" || { echo "STEADY_STATE_RECOMPILE"; exit 1; }

  # scheduled-driver smoke (ISSUE 5 satellite): the same tiny scanned
  # run under throughput-aware sampling + a 0.9-quantile deadline; its
  # journal (schedule events, per-round byte totals) must pass the
  # same invariant check, so the scheduler's record format cannot rot.
  JR2=/tmp/_t1_journal_sched.jsonl
  rm -f "$JR2"
  timeout -k 10 300 env JAX_PLATFORMS=cpu \
      XLA_FLAGS="--xla_force_host_platform_device_count=8" \
      python -m commefficient_tpu.training.cv_train \
      --test --dataset_name CIFAR10 --mode uncompressed \
      --local_momentum 0.0 --num_workers 8 --local_batch_size 8 \
      --num_epochs 0.05 --valid_batch_size 16 --lr_scale 0.1 \
      --scan_rounds --scan_span 1 --debug_transfer_guard \
      --sampler throughput --deadline_quantile 0.9 \
      --journal_path "$JR2" --dataset_dir /tmp/_t1_ds >/dev/null 2>&1 \
      || { echo "SCHEDULED_SMOKE_FAILED"; exit 1; }
  python scripts/journal_summary.py "$JR2" \
      || { echo "SCHED_JOURNAL_INVALID"; exit 1; }
  check_no_recompiles "$JR2" || { echo "SCHED_RECOMPILE"; exit 1; }

  # Pallas kernel-backend gate (ISSUE 6 satellite). Two parts:
  # (1) the `pallas` marker suite alone — the kernels' interpret-mode
  #     equivalence/property tests must be green on CPU (they also
  #     ran inside the main sweep above;
  #     this dedicated pass keeps the gate visible and cheap to rerun);
  # (2) a driver smoke on the fused-kernel backend with a bf16 wire
  #     table (small sketch geometry so the CPU interpreter finishes),
  #     whose journal must validate — the record format carries the
  #     corrected wire-dtype byte totals and must not rot.
  timeout -k 10 300 env JAX_PLATFORMS=cpu python -m pytest tests/ -q \
      -m pallas -p no:cacheprovider -p no:xdist -p no:randomly \
      >/dev/null 2>&1 || { echo "PALLAS_SUITE_FAILED"; exit 1; }
  JR3=/tmp/_t1_journal_pallas.jsonl
  rm -f "$JR3"
  timeout -k 10 300 env JAX_PLATFORMS=cpu \
      XLA_FLAGS="--xla_force_host_platform_device_count=8" \
      python -m commefficient_tpu.training.cv_train \
      --test --dataset_name CIFAR10 --mode sketch \
      --error_type virtual --virtual_momentum 0.9 \
      --local_momentum 0.0 --num_workers 8 --local_batch_size 8 \
      --num_epochs 0.05 --valid_batch_size 16 --lr_scale 0.1 \
      --k 64 --num_rows 3 --num_cols 256 --num_blocks 1 \
      --kernel_backend pallas --sketch_table_dtype bf16 \
      --journal_path "$JR3" --dataset_dir /tmp/_t1_ds >/dev/null 2>&1 \
      || { echo "PALLAS_SMOKE_FAILED"; exit 1; }
  python scripts/journal_summary.py "$JR3" \
      || { echo "PALLAS_JOURNAL_INVALID"; exit 1; }

  # pipelined-driver smoke (ISSUE 10 satellite): the same tiny scanned
  # run under --pipeline (double-buffered dispatch + writer-thread
  # journal/checkpoint persistence) with --async_admit_rounds 1 and a
  # heavy random-straggler load — the production twin of
  # FaultSchedule.slow (both feed the same work-fraction operand) —
  # plus per-span rotated checkpoints so the async checkpoint writer
  # runs end-to-end. The journal it writes (round/span/checkpoint
  # events from the one-span-late commit path) must pass the same
  # invariant check, so the pipelined record stream cannot rot.
  # ISSUE 13 rides the same smoke with --trace: the graftscope spans
  # must validate, export to well-formed Chrome trace JSON covering
  # >= 5 distinct stages across >= 3 threads, and the summary must
  # report per-stage p50/p95 plus a nonzero overlap efficiency.
  JR5=/tmp/_t1_journal_pipe.jsonl
  rm -f "$JR5" "$JR5.trace.json"
  rm -rf /tmp/_t1_pipe_ckpt
  timeout -k 10 300 env JAX_PLATFORMS=cpu \
      XLA_FLAGS="--xla_force_host_platform_device_count=8" \
      python -m commefficient_tpu.training.cv_train \
      --test --dataset_name CIFAR10 --mode uncompressed \
      --local_momentum 0.0 --num_workers 8 --local_batch_size 8 \
      --num_epochs 0.05 --valid_batch_size 16 --lr_scale 0.1 \
      --scan_rounds --scan_span 1 --pipeline --async_admit_rounds 1 \
      --straggler_rate 0.6 --straggler_min_work 0.4 \
      --checkpoint --checkpoint_every 1 \
      --checkpoint_path /tmp/_t1_pipe_ckpt --trace \
      --journal_path "$JR5" --dataset_dir /tmp/_t1_ds >/dev/null 2>&1 \
      || { echo "PIPELINE_SMOKE_FAILED"; exit 1; }
  python scripts/journal_summary.py "$JR5" \
      || { echo "PIPELINE_JOURNAL_INVALID"; exit 1; }
  check_no_recompiles "$JR5" || { echo "PIPELINE_RECOMPILE"; exit 1; }
  python scripts/trace_export.py "$JR5" -o "$JR5.trace.json" \
      || { echo "TRACE_EXPORT_FAILED"; exit 1; }
  python - "$JR5" "$JR5.trace.json" <<'PYEOF' || { echo "TRACE_GATE_FAILED"; exit 1; }
import json, sys
sys.path.insert(0, ".")
from commefficient_tpu.telemetry.journal import summarize, validate_journal
records, problems = validate_journal(sys.argv[1])
assert not problems, problems
s = summarize(records)
assert s.get("trace_spans", 0) > 0, "no graftscope spans journaled"
stages = s.get("trace_stages", {})
assert all("p50_s" in v and "p95_s" in v for v in stages.values())
oe = s.get("overlap_efficiency")
assert oe is not None and oe > 0, f"overlap efficiency not measured: {oe}"
trace = json.load(open(sys.argv[2]))
xs = [e for e in trace["traceEvents"] if e.get("ph") == "X"]
names = {e["name"] for e in xs}
threads = {(e["pid"], e["tid"]) for e in xs}
assert len(names) >= 5, f"only {len(names)} stages exported: {sorted(names)}"
assert len(threads) >= 3, f"only {len(threads)} threads in trace"
print(f"TRACE_GATE_OK stages={len(names)} threads={len(threads)} "
      f"overlap_efficiency={oe}")
PYEOF

  # self-tuning control smoke (ISSUE 20): the pipelined smoke's
  # heavy-straggler load with all three feedback controllers live —
  # cohort speed matching (--speed_match), adaptive span cadence
  # (--scan_span_palette, spans retraced once at warmup then picked
  # from the palette), and adaptive staleness decay
  # (--adapt_staleness, fixed-lag stamped from the estimate-residual
  # metric). Gates: the journal validates (control event schema),
  # summarize() shows >= 1 journaled adjustment for EACH controller
  # (a silently-inert controller fails), and the steady-state loop
  # journals zero compile_warning — the palette's span programs all
  # traced at warmup, so adaptation costs no recompiles.
  JR12=/tmp/_t1_journal_control.jsonl
  rm -f "$JR12"
  timeout -k 10 300 env JAX_PLATFORMS=cpu \
      XLA_FLAGS="--xla_force_host_platform_device_count=8" \
      python -m commefficient_tpu.training.cv_train \
      --test --dataset_name CIFAR10 --mode uncompressed \
      --local_momentum 0.0 --num_workers 8 --local_batch_size 8 \
      --num_epochs 0.5 --valid_batch_size 16 --lr_scale 0.1 \
      --scan_rounds --scan_span_palette 1,2 --pipeline \
      --sampler throughput --async_admit_rounds 1 \
      --speed_match --adapt_staleness \
      --straggler_rate 0.6 --straggler_min_work 0.4 \
      --journal_path "$JR12" --dataset_dir /tmp/_t1_ds >/dev/null 2>&1 \
      || { echo "CONTROL_SMOKE_FAILED"; exit 1; }
  python scripts/journal_summary.py "$JR12" \
      || { echo "CONTROL_JOURNAL_INVALID"; exit 1; }
  check_no_recompiles "$JR12" || { echo "CONTROL_RECOMPILE"; exit 1; }
  python - "$JR12" <<'PYEOF' || { echo "CONTROL_GATE_FAILED"; exit 1; }
import sys
sys.path.insert(0, ".")
from commefficient_tpu.telemetry.journal import summarize, validate_journal
records, problems = validate_journal(sys.argv[1])
assert not problems, problems
ctls = summarize(records).get("controllers", {})
want = {"speed_match", "span_cadence", "staleness_decay"}
assert set(ctls) >= want, \
    f"controllers missing from journal: {sorted(want - set(ctls))}"
inert = [n for n in want if ctls[n]["adjustments"] < 1]
assert not inert, f"controller(s) never adjusted: {inert}"
print("CONTROL_GATE_OK " + " ".join(
    f"{n}={ctls[n]['adjustments']}/{ctls[n]['final']}"
    for n in sorted(want)))
PYEOF

  # multi-controller control-plane smoke (ISSUE 12): the scheduled
  # scanned run under the EMULATED N-controller plan transport —
  # throughput sampling + async admission, every round's plan
  # broadcast, installed on every controller, digest-cross-checked
  # and write-ahead journaled — with a scripted coordinator crash
  # (CCTPU_EMU_COORD_CRASH) mid-run. The first run must FAIL at the
  # injected crash, the --resume run must complete from the last
  # persisted boundary, and the combined write-ahead plan journal
  # must validate.
  JR7=/tmp/_t1_journal_ctrl.jsonl
  rm -f "$JR7"
  rm -rf /tmp/_t1_ctrl_ckpt
  if timeout -k 10 300 env JAX_PLATFORMS=cpu \
      XLA_FLAGS="--xla_force_host_platform_device_count=8" \
      CCTPU_EMU_COORD_CRASH=1 \
      python -m commefficient_tpu.training.cv_train \
      --test --dataset_name CIFAR10 --mode uncompressed \
      --local_momentum 0.0 --num_workers 8 --local_batch_size 8 \
      --num_epochs 0.05 --valid_batch_size 16 --lr_scale 0.1 \
      --scan_rounds --scan_span 1 \
      --sampler throughput --async_admit_rounds 1 \
      --straggler_rate 0.5 --straggler_min_work 0.4 \
      --plan_transport emulated \
      --checkpoint --checkpoint_every 1 \
      --checkpoint_path /tmp/_t1_ctrl_ckpt \
      --journal_path "$JR7" --dataset_dir /tmp/_t1_ds \
      >/dev/null 2>&1; then
    echo "CTRL_SMOKE_CRASH_NOT_INJECTED"; exit 1
  fi
  timeout -k 10 300 env JAX_PLATFORMS=cpu \
      XLA_FLAGS="--xla_force_host_platform_device_count=8" \
      python -m commefficient_tpu.training.cv_train \
      --test --dataset_name CIFAR10 --mode uncompressed \
      --local_momentum 0.0 --num_workers 8 --local_batch_size 8 \
      --num_epochs 0.05 --valid_batch_size 16 --lr_scale 0.1 \
      --scan_rounds --scan_span 1 \
      --sampler throughput --async_admit_rounds 1 \
      --straggler_rate 0.5 --straggler_min_work 0.4 \
      --plan_transport emulated \
      --checkpoint --checkpoint_every 1 \
      --checkpoint_path /tmp/_t1_ctrl_ckpt \
      --journal_path "$JR7" --dataset_dir /tmp/_t1_ds --resume \
      >/dev/null 2>&1 \
      || { echo "CTRL_SMOKE_RESUME_FAILED"; exit 1; }
  python scripts/journal_summary.py "$JR7" \
      || { echo "CTRL_JOURNAL_INVALID"; exit 1; }
  python - "$JR7" <<'PYEOF' || { echo "CTRL_NO_DIGESTS"; exit 1; }
import json, sys
digs = [json.loads(l).get("digest") for l in open(sys.argv[1])
        if '"schedule"' in l]
assert digs and all(isinstance(d, str) and len(d) == 64 for d in digs), \
    "control-plane smoke journaled no write-ahead plan digests"
PYEOF

  # poisoned-driver smoke (ISSUE 16 satellite): the telemetry smoke's
  # config with value-fault injection live (--poison_rate 0.1 NaN
  # poison on the deterministic per-round PRNG domain) and in-round
  # finite screening admitting the poisoned clients out. Gates: the
  # journal validates (screened event schema), summarize() shows
  # nonzero screened_total with zero numeric_trips (screening caught
  # every fault BEFORE the telemetry tripwire), and the final rotated
  # checkpoint's server weights are finite — poison never reached the
  # aggregate.
  JR8=/tmp/_t1_journal_poison.jsonl
  rm -f "$JR8"
  rm -rf /tmp/_t1_poison_ckpt
  timeout -k 10 300 env JAX_PLATFORMS=cpu \
      XLA_FLAGS="--xla_force_host_platform_device_count=8" \
      python -m commefficient_tpu.training.cv_train \
      --test --dataset_name CIFAR10 --mode uncompressed \
      --local_momentum 0.0 --num_workers 8 --local_batch_size 8 \
      --num_epochs 0.05 --valid_batch_size 16 --lr_scale 0.1 \
      --scan_rounds --scan_span 1 \
      --poison_rate 0.1 --poison_kind nan --update_screen finite \
      --checkpoint --checkpoint_every 1 \
      --checkpoint_path /tmp/_t1_poison_ckpt \
      --journal_path "$JR8" --dataset_dir /tmp/_t1_ds >/dev/null 2>&1 \
      || { echo "POISON_SMOKE_FAILED"; exit 1; }
  python scripts/journal_summary.py "$JR8" \
      || { echo "POISON_JOURNAL_INVALID"; exit 1; }
  python - "$JR8" <<'PYEOF' || { echo "POISON_GATE_FAILED"; exit 1; }
import sys
import numpy as np
sys.path.insert(0, ".")
from commefficient_tpu.telemetry.journal import summarize, validate_journal
from commefficient_tpu.utils.checkpoint import load_resilient
records, problems = validate_journal(sys.argv[1])
assert not problems, problems
s = summarize(records)
assert s.get("screened_total", 0) > 0, \
    "poisoned smoke screened nobody — injection or admission inactive"
assert s.get("numeric_trips", 0) == 0, \
    "screening let poison through to the telemetry tripwire"
loaded = load_resilient("/tmp/_t1_poison_ckpt/ResNet9")
assert loaded is not None, "poisoned smoke left no loadable checkpoint"
_, ckpt = loaded
assert np.isfinite(np.asarray(ckpt.server.ps_weights)).all(), \
    "non-finite final weights after a screened poisoned run"
print(f"POISON_GATE_OK screened_total={s['screened_total']}")
PYEOF

  # adversarial smoke (ISSUE 17): the poisoned smoke's config with a
  # LIVE Byzantine cohort — 20% sign-flip attackers on the dedicated
  # adversary PRNG domain — aggregated with the beta-trimmed mean and
  # norm screening under the plan-driven adaptive controller
  # (--target_screened_rate). Gates: the journal validates (aggregator
  # + screen_adapt event schemas), summarize() shows nonzero
  # trimmed_total (the order statistics actually rejected cells) and
  # >= 1 screen_adaptation (the multiplier trajectory moved, riding
  # journaled RoundPlans), and the final rotated checkpoint's server
  # weights are finite — the attack never reached the aggregate.
  JR9=/tmp/_t1_journal_byz.jsonl
  rm -f "$JR9"
  rm -rf /tmp/_t1_byz_ckpt
  timeout -k 10 300 env JAX_PLATFORMS=cpu \
      XLA_FLAGS="--xla_force_host_platform_device_count=8" \
      python -m commefficient_tpu.training.cv_train \
      --test --dataset_name CIFAR10 --mode uncompressed \
      --local_momentum 0.0 --num_workers 8 --local_batch_size 8 \
      --num_epochs 0.05 --valid_batch_size 16 --lr_scale 0.1 \
      --scan_rounds --scan_span 1 \
      --byzantine_rate 0.2 --attack sign_flip \
      --aggregator trimmed_mean --update_screen norm \
      --target_screened_rate 0.05 \
      --checkpoint --checkpoint_every 1 \
      --checkpoint_path /tmp/_t1_byz_ckpt \
      --journal_path "$JR9" --dataset_dir /tmp/_t1_ds >/dev/null 2>&1 \
      || { echo "BYZANTINE_SMOKE_FAILED"; exit 1; }
  python scripts/journal_summary.py "$JR9" \
      || { echo "BYZANTINE_JOURNAL_INVALID"; exit 1; }
  python - "$JR9" <<'PYEOF' || { echo "BYZANTINE_GATE_FAILED"; exit 1; }
import sys
import numpy as np
sys.path.insert(0, ".")
from commefficient_tpu.telemetry.journal import summarize, validate_journal
from commefficient_tpu.utils.checkpoint import load_resilient
records, problems = validate_journal(sys.argv[1])
assert not problems, problems
s = summarize(records)
assert s.get("trimmed_total", 0) > 0, \
    "adversarial smoke trimmed nothing — attack or robust path inactive"
assert s.get("screen_adaptations", 0) >= 1, \
    "adaptive screening never adjusted the multiplier"
loaded = load_resilient("/tmp/_t1_byz_ckpt/ResNet9")
assert loaded is not None, "adversarial smoke left no loadable checkpoint"
_, ckpt = loaded
assert np.isfinite(np.asarray(ckpt.server.ps_weights)).all(), \
    "non-finite final weights after a robust-aggregated attacked run"
print(f"BYZANTINE_GATE_OK trimmed_total={s['trimmed_total']} "
      f"screen_adaptations={s['screen_adaptations']}")
PYEOF

  # large-population smoke (ISSUE 9 satellite): the O(active) refactor
  # driven end-to-end at a 100k-client population with the --test tiny
  # model (D=100) and local_topk + local error + momentum + topk_down,
  # so all three sharded state blocks exist and the cohort
  # gather/scatter, sparse accountant/tracker, and O(cohort)
  # checkpointless round path all run against a population 10,000x the
  # cohort. Same 8-device host mesh as the mesh-audit step; the
  # journal must validate.
  JR4=/tmp/_t1_journal_pop.jsonl
  rm -f "$JR4"
  timeout -k 10 500 env JAX_PLATFORMS=cpu \
      XLA_FLAGS="--xla_force_host_platform_device_count=8" \
      python -m commefficient_tpu.training.cv_train \
      --test --dataset_name CIFAR10 --mode local_topk \
      --error_type local --local_momentum 0.9 --topk_down \
      --num_clients 100000 --num_workers 8 --local_batch_size 8 \
      --num_epochs 0.05 --valid_batch_size 16 --lr_scale 0.1 \
      --journal_path "$JR4" --dataset_dir /tmp/_t1_ds >/dev/null 2>&1 \
      || { echo "POPULATION_SMOKE_FAILED"; exit 1; }
  python scripts/journal_summary.py "$JR4" \
      || { echo "POPULATION_JOURNAL_INVALID"; exit 1; }

  # tiered-state smoke (ISSUE 11 satellite): the same local_topk
  # workload behind --state_tier host with a working set SMALLER than
  # the clients the run touches, so restores and spills happen
  # mid-run on the bounded-queue spill writer. The journal must
  # validate (state_tier event schema) and must show nonzero spills —
  # a silently-inactive tier fails the gate.
  JR6=/tmp/_t1_journal_tier.jsonl
  rm -f "$JR6"
  timeout -k 10 500 env JAX_PLATFORMS=cpu \
      XLA_FLAGS="--xla_force_host_platform_device_count=8" \
      python -m commefficient_tpu.training.cv_train \
      --test --dataset_name CIFAR10 --mode local_topk \
      --error_type local --local_momentum 0.9 --topk_down \
      --num_clients 100 --num_workers 8 --local_batch_size 8 \
      --state_tier host --state_working_set 16 \
      --num_epochs 2 --valid_batch_size 16 --lr_scale 0.1 \
      --journal_path "$JR6" --dataset_dir /tmp/_t1_ds >/dev/null 2>&1 \
      || { echo "TIER_SMOKE_FAILED"; exit 1; }
  python scripts/journal_summary.py "$JR6" \
      || { echo "TIER_JOURNAL_INVALID"; exit 1; }
  python - "$JR6" <<'PYEOF' || { echo "TIER_NO_SPILLS"; exit 1; }
import json, sys
spills = sum(json.loads(l).get("spills", 0)
             for l in open(sys.argv[1])
             if '"state_tier"' in l)
assert spills > 0, "tiered smoke journaled zero spills"
PYEOF

  # PowerSGD compressor smoke (ISSUE 19): the telemetry smoke's config
  # on the rank-2 low-rank plugin (local error feedback, warm-started
  # Q factors in the velocities block). Gates: the journal validates
  # (compressor event schema) and every round journals a compressor
  # event with the factor-wire byte total — a plugin that bills the
  # dense gradient instead of (m+n)*rank factors fails here.
  JR10=/tmp/_t1_journal_psgd.jsonl
  rm -f "$JR10"
  timeout -k 10 300 env JAX_PLATFORMS=cpu \
      XLA_FLAGS="--xla_force_host_platform_device_count=8" \
      python -m commefficient_tpu.training.cv_train \
      --test --dataset_name CIFAR10 --mode powersgd \
      --powersgd_rank 2 --error_type local --local_momentum 0.0 \
      --num_workers 8 --local_batch_size 8 \
      --num_epochs 0.05 --valid_batch_size 16 --lr_scale 0.1 \
      --journal_path "$JR10" --dataset_dir /tmp/_t1_ds >/dev/null 2>&1 \
      || { echo "POWERSGD_SMOKE_FAILED"; exit 1; }
  python scripts/journal_summary.py "$JR10" \
      || { echo "POWERSGD_JOURNAL_INVALID"; exit 1; }
  python - "$JR10" <<'PYEOF' || { echo "POWERSGD_GATE_FAILED"; exit 1; }
import json, sys
evs = [json.loads(l) for l in open(sys.argv[1]) if '"compressor"' in l]
evs = [e for e in evs if e.get("event") == "compressor"]
assert evs, "powersgd smoke journaled no compressor events"
assert all(e["mode"] == "powersgd" for e in evs), evs[:3]
assert all(e["wire_bytes"] > 0 for e in evs), evs[:3]
print(f"POWERSGD_GATE_OK rounds={len(evs)} "
      f"wire_bytes={evs[0]['wire_bytes']}")
PYEOF

  # DP-sketch compressor smoke (ISSUE 19): the sketch smoke's geometry
  # with per-client l2 clipping and calibrated Gaussian noise on the
  # registered "dp" PRNG domain, under a live --dp_target_epsilon
  # budget. Gates: the journal validates (privacy event schema), every
  # committed round journals a privacy event, the cumulative epsilon
  # trajectory is non-decreasing and stays under the budget the run
  # was given (sigma is sized so the smoke cannot exhaust it), and
  # summarize() surfaces the spend.
  JR11=/tmp/_t1_journal_dp.jsonl
  rm -f "$JR11"
  timeout -k 10 300 env JAX_PLATFORMS=cpu \
      XLA_FLAGS="--xla_force_host_platform_device_count=8" \
      python -m commefficient_tpu.training.cv_train \
      --test --dataset_name CIFAR10 --mode dp_sketch \
      --error_type virtual --virtual_momentum 0.9 \
      --local_momentum 0.0 --num_workers 8 --local_batch_size 8 \
      --num_epochs 0.05 --valid_batch_size 16 --lr_scale 0.1 \
      --k 64 --num_rows 3 --num_cols 256 --num_blocks 1 \
      --dp_clip 1.0 --dp_noise_mult 4.0 --dp_target_epsilon 8 \
      --journal_path "$JR11" --dataset_dir /tmp/_t1_ds >/dev/null 2>&1 \
      || { echo "DP_SMOKE_FAILED"; exit 1; }
  python scripts/journal_summary.py "$JR11" \
      || { echo "DP_JOURNAL_INVALID"; exit 1; }
  python - "$JR11" <<'PYEOF' || { echo "DP_GATE_FAILED"; exit 1; }
import json, sys
sys.path.insert(0, ".")
from commefficient_tpu.telemetry.journal import summarize, validate_journal
records, problems = validate_journal(sys.argv[1])
assert not problems, problems
evs = [r for r in records if r.get("event") == "privacy"]
assert evs, "dp_sketch smoke journaled no privacy events"
eps = [e["epsilon"] for e in evs]
assert all(b >= a for a, b in zip(eps, eps[1:])), \
    f"epsilon trajectory not monotone: {eps}"
assert eps[-1] <= 8.0, f"smoke exceeded its own budget: {eps[-1]}"
s = summarize(records)
assert s.get("epsilon_spent") == eps[-1], s.get("epsilon_spent")
assert "dp_sketch" in s.get("compressor_modes", {}), \
    s.get("compressor_modes")
print(f"DP_GATE_OK rounds={len(evs)} epsilon_spent={eps[-1]}")
PYEOF
fi
exit $rc

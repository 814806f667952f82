"""CV federated training driver.

The reference driver's launch surface re-created on the TPU runtime
(reference: CommEfficient/cv_train.py — loss/metric callbacks :32-83,
epoch loop `train`/`run_batches` :85-250, loader construction
:254-287, `__main__` wiring :289-421): same flags (config.parse_args),
same loss-callback contract, same TableLogger output columns, same
communication-MiB reporting, same --test smoke shrink, NaN abort,
checkpoint and head-swap finetune. Differences are the TPU runtime
underneath (one jitted SPMD round instead of processes+NCCL) and one
addition the reference cannot express: --scan_rounds runs a whole
epoch of rounds as a single scanned device program
(FedModel.run_rounds), amortizing all host dispatch.

Run: python -m commefficient_tpu.training.cv_train --dataset_name
CIFAR10 --mode sketch --error_type virtual ...
"""
from __future__ import annotations

import contextlib
import math
import os
from typing import Optional

import jax

import jax.numpy as jnp
import numpy as np

from commefficient_tpu import models
from commefficient_tpu.config import Config, num_classes_of_dataset, parse_args
from commefficient_tpu.data import (
    FedCIFAR10, FedCIFAR100, FedEMNIST, FedImageNet, FedLoader,
    FedValLoader, transforms,
)
from commefficient_tpu.federated.api import FedModel, FedOptimizer
from commefficient_tpu.parallel import multihost as mh
from commefficient_tpu.utils.cache import enable_persistent_compilation_cache
from commefficient_tpu.training.scanloop import (
    make_span_checkpoint, numeric_rollback, run_scanned_rounds,
)
from commefficient_tpu.utils.checkpoint import (
    latest_checkpoint_path, load_checkpoint, load_resilient,
    save_final, save_rotating, transfer_for_finetune,
)
from commefficient_tpu.telemetry.trace import TRACE
from commefficient_tpu.utils.logging import (
    TableLogger, Timer, make_logdir,
)
from commefficient_tpu.utils.schedules import LambdaLR, PiecewiseLinear


# ---------------- loss callbacks (reference cv_train.py:32-83) -----------

def make_compute_loss(model):
    """Masked cross-entropy + accuracy under the framework's loss
    contract: loss_fn(params, (images, labels), mask) ->
    (mean loss, (mean accuracy,))."""

    def compute_loss(params, batch, mask):
        images, labels = batch
        logits = model.apply(params, images)
        logp = jax.nn.log_softmax(logits)
        nll = -jnp.take_along_axis(logp, labels[:, None].astype(jnp.int32),
                                   axis=1)[:, 0]
        denom = jnp.maximum(mask.sum(), 1.0)
        loss = (nll * mask).sum() / denom
        acc = ((logits.argmax(-1) == labels) * mask).sum() / denom
        return loss, (acc,)

    return compute_loss


# ---------------- data (reference cv_train.py:254-287) -------------------

# name -> (dataset class, transform factory, --test synthetic sizes)
# (the reference routes all four CV datasets the same way,
# cv_train.py:254-287; EMNIST synthetic sizes are (writers, imgs/writer),
# ImageNet's are (train, val) — see each dataset's docstring)
_DATASETS = {
    "CIFAR10": (FedCIFAR10, transforms.cifar10_transforms, (2048, 512)),
    "CIFAR100": (FedCIFAR100, transforms.cifar100_transforms, (2048, 512)),
    "EMNIST": (FedEMNIST, transforms.femnist_transforms, (64, 16)),
    "ImageNet": (FedImageNet, transforms.imagenet_transforms, (512, 64)),
}


def get_data_loaders(cfg: Config):
    try:
        dataset_cls, transform_factory, test_sizes = _DATASETS[cfg.dataset_name]
    except KeyError:
        raise ValueError(
            f"cv_train supports {sorted(_DATASETS)}; for PERSONA use "
            f"gpt2_train (reference split is the same, cv_train.py vs "
            f"gpt2_train.py)")
    train_t, test_t = transform_factory(seed=cfg.seed)
    # --test smoke: generate a small synthetic dataset when the real
    # archives aren't on disk (the reference's --test mode likewise
    # bypasses real compute, fed_worker.py:117-122)
    synthetic = test_sizes if cfg.do_test else None
    train_set = dataset_cls(
        cfg.dataset_dir, transform=train_t, do_iid=cfg.do_iid,
        num_clients=cfg.num_clients, train=True, seed=cfg.seed,
        synthetic_examples=synthetic)
    val_set = dataset_cls(
        cfg.dataset_dir, transform=test_t, do_iid=cfg.do_iid,
        num_clients=cfg.num_clients, train=False, seed=cfg.seed,
        synthetic_examples=synthetic)
    train_loader = FedLoader(train_set, cfg.num_workers,
                             cfg.local_batch_size, seed=cfg.seed,
                             max_local_batch=cfg.max_local_batch)
    val_loader = FedValLoader(val_set, cfg.valid_batch_size,
                              num_shards=min(jax.device_count(),
                                             cfg.num_workers))
    return train_loader, val_loader


# ---------------- training loop (reference cv_train.py:85-250) -----------

def run_eval(model: FedModel, val_loader) -> tuple:
    model.train(False)
    tot_loss = tot_acc = tot_n = 0.0
    for data, mask in val_loader.batches():
        loss, acc, count = model((data, mask))
        n = count.sum()
        tot_loss += float((loss * count).sum())
        tot_acc += float((acc * count).sum())
        tot_n += float(n)
    model.train(True)
    denom = max(tot_n, 1.0)
    return tot_loss / denom, tot_acc / denom


def train(model: FedModel, opt: FedOptimizer, lr_scheduler,
          train_loader, val_loader, cfg: Config,
          loggers=(), timer: Optional[Timer] = None, log_dir: str = ""):
    timer = timer or Timer()
    # --debug_transfer_guard: forbid implicit host<->device transfers
    # in the steady-state loop — every span/round after the first
    # (which compiles) dispatches under the guard, so a hidden
    # per-round sync raises instead of silently stalling the device
    guard = None
    if cfg.debug_transfer_guard:
        from commefficient_tpu.analysis.runtime import forbid_transfers
        guard = forbid_transfers
    # first dispatch of THIS PROCESS compiles (also after a resume, so
    # this is a process-local flag, not round count)
    warmed = [False]
    spe = train_loader.steps_per_epoch
    total_rounds = math.ceil(cfg.num_epochs * spe)
    # on resume, num_epochs is the TOTAL budget: rounds already done
    # (restored round_idx) count against it
    rounds_done = int(model.server.round_idx)
    epoch = rounds_done // spe
    # mid-epoch resume: fast-forward the first resumed epoch's stream
    # past the rounds already trained — sampler index math only, no
    # batch materialization (FedLoader.epoch(skip=); symmetric with
    # gpt2_train's fast-forward). With checkpointed sampler state
    # (smp_* keys restored by model.load_state), resolve_resume
    # collapses the skip to 0: the restored cursor CONTINUES the
    # stream exactly, so non-uniform sampling resumes onto the same
    # data the uninterrupted run would have fed.
    skip_rounds = train_loader.sampler.resolve_resume(
        rounds_done % spe)
    # restored mid-epoch stream: the uninterrupted run caps every
    # epoch at spe rounds, so a stream restored AT the cap was
    # abandoned right there (discard — the restored rng is all a
    # fresh epoch needs), and one restored short of the cap may only
    # be driven for the REMAINING spe - pos rounds (the scanned
    # epoch_rounds budget below subtracts resumed_pos; without the
    # subtraction a resumed epoch would overrun the cap on the same
    # permutation)
    resumed_pos = train_loader.sampler.pending_pos or 0
    if resumed_pos >= spe:
        train_loader.sampler.discard_pending()
        resumed_pos = 0
    # byte totals are plain scalars: the accountant's per-round rows
    # are COHORT-indexed since ISSUE 9 — a per-population accumulator
    # here was an O(num_clients) host allocation per epoch
    total_down = 0.0
    total_up = 0.0

    writer = None
    if cfg.use_tensorboard and mh.is_coordinator():
        writer = _try_tensorboard(log_dir)

    profiling = False
    profiled = False
    while rounds_done < total_rounds:
        epoch += 1
        if cfg.do_profile and not profiled:
            # device-level trace of the first trained epoch (compile +
            # steady-state rounds), viewable in TensorBoard/Perfetto
            jax.profiler.start_trace(
                os.path.join(log_dir or ".", "profile"))
            profiling = profiled = True
        epoch_rounds = min(spe - resumed_pos,
                           total_rounds - rounds_done)
        if model.scheduler is not None:
            # sync the scheduler's round counter to the stream about
            # to be drawn: the resumed first epoch replays (and
            # re-selects, without dispatching) its skipped head, so
            # the counter starts at the EPOCH's first round
            model.scheduler.begin_epoch(rounds_done - skip_rounds)
        epoch_stream = train_loader.epoch(skip=skip_rounds)
        skip_rounds = 0
        resumed_pos = 0
        losses, accs = [], []
        down = 0.0
        up = 0.0

        # EMNIST prints one line per STEP (reference cv_train.py:233-237)
        per_step_log = (cfg.dataset_name == "EMNIST"
                        and mh.is_coordinator())
        step_t0 = [_now()]
        # scan mode has no per-round boundaries — rounds of a span all
        # emit at flush — so Time is the span-amortized per-round value
        # (set by on_flush); the unscanned path measures each step
        amortized = [0.0]

        def step_line(lr, elapsed):
            print("LR: {:0.5f}, Loss: {:0.5f}, Acc: {:0.5f}, "
                  "Time: {:0.2f}".format(float(lr), losses[-1], accs[-1],
                                         elapsed))

        if cfg.scan_rounds:
            # scanned device programs, flushed every --scan_span rounds
            # to bound the staged [N, W, B, ...] arrays (0 = whole
            # epoch); staging/flush mechanics shared with gpt2_train
            # (training/scanloop.py)
            taken = 0


            def stream():
                # cap-BEFORE-pull: the epoch budget is checked before
                # drawing the next round, so ending an epoch never
                # draws-and-discards a round (a phantom rng advance no
                # resume could reproduce), and the abandonment mark
                # lands before any checkpoint that follows — a resume
                # from the epoch's last span checkpoint (pos == cap)
                # discards the restored stream exactly where this run
                # abandons it
                nonlocal taken
                stream_it = iter(epoch_stream)
                while taken < epoch_rounds:
                    try:
                        client_ids, data, mask = next(stream_it)
                    except StopIteration:
                        return
                    lr_scheduler.step()
                    taken += 1
                    lr = opt.param_groups[0]["lr"]
                    yield (lr, client_ids, data, mask, lr)
                train_loader.sampler.abandon_epoch()

            def on_flush(n_rounds):
                amortized[0] = (_now() - step_t0[0]) / max(n_rounds, 1)
                step_t0[0] = _now()

            def scan_emit(lr, loss_w, acc_w):
                losses.append(float(np.mean(loss_w)))
                accs.append(float(np.mean(acc_w)))
                if per_step_log:
                    step_line(lr, amortized[0])
                return True  # NaN abort handled by the epoch-mean check

            def on_comm(d, u):
                nonlocal down, up
                down += float(np.sum(d))
                up += float(np.sum(u))

            run_scanned_rounds(
                model, stream(),
                # palette mode hands the controller bank in as the
                # adaptive span provider; static --scan_span otherwise
                model.control_bank if cfg.span_palette
                else (cfg.scan_span if cfg.scan_span > 0
                      else epoch_rounds),
                scan_emit, on_comm, on_flush=on_flush,
                # span-boundary saves bound a mid-span preemption's
                # loss to ckpt_every_spans spans, not one epoch
                checkpoint=make_span_checkpoint(
                    _ckpt_path(cfg), model, cfg, lr_scheduler),
                guard=guard,
                # --pipeline: double-buffered dispatch — span t+1
                # stages/dispatches while span t runs on device and
                # span t-1 persists (ISSUE 10)
                pipeline=cfg.pipeline)
            rounds_done += taken
        else:
            # metrics materialize with a ONE-ROUND lag: float()ing the
            # round just dispatched would block the host on the device
            # every round; round
            # t-1's values are already computed, so float() is free.
            # NaN abort latency grows by exactly one round.
            def emit(p) -> bool:
                # gather_host: per-client metrics are cross-process
                # sharded in multi-controller runs (np.asarray in
                # single-process ones)
                losses.append(float(np.mean(mh.gather_host(p[0]))))
                accs.append(float(np.mean(mh.gather_host(p[1]))))
                if per_step_log:
                    step_line(p[2], _now() - step_t0[0])
                    step_t0[0] = _now()
                return not np.isnan(losses[-1])

            pending = None
            stream_it = iter(epoch_stream)
            while True:
                if rounds_done >= total_rounds:
                    # round budget reached mid-stream: abandon
                    # WITHOUT pulling (see the scanned cap above) so
                    # any later checkpoint records in_epoch=0
                    train_loader.sampler.abandon_epoch()
                    break
                try:
                    client_ids, data, mask = next(stream_it)
                except StopIteration:
                    break
                lr_scheduler.step()
                # first dispatch of the process compiles; every later
                # one is steady state and runs under the (optional)
                # transfer guard — same warmup exemption as the
                # scanned path
                ctx = (guard() if guard is not None and warmed[0]
                       else contextlib.nullcontext())
                with ctx:
                    loss, acc, d, u = model((client_ids, data, mask))
                warmed[0] = True
                opt.step()
                down += float(np.sum(d))
                up += float(np.sum(u))
                if pending is not None and not emit(pending):
                    pending = None
                    break
                pending = (loss, acc, opt.param_groups[0]["lr"])
                rounds_done += 1
            if pending is not None:
                emit(pending)

        total_down += down
        total_up += up
        if profiling:
            jax.profiler.stop_trace()
            profiling = False
            print(f"profile trace written to "
                  f"{os.path.join(log_dir or '.', 'profile')}")
        train_time = timer()

        mean_loss = float(np.mean(losses)) if losses else float("nan")
        mean_acc = float(np.mean(accs)) if accs else float("nan")

        # NaN abort (reference cv_train.py:110-112,222-224); every
        # controller computes the same mean, so all abort together
        if np.isnan(mean_loss) or mean_loss > cfg.nan_threshold:
            if mh.is_coordinator():
                print(f"found nan/divergent loss {mean_loss}, aborting")
            return False

        val_loss, val_acc = run_eval(model, val_loader)
        val_time = timer()

        row = {
            "epoch": epoch,
            "lr": round(float(opt.param_groups[0]["lr"]), 5),
            "train_time": train_time,
            "train_loss": mean_loss,
            "train_acc": mean_acc,
            "test_time": val_time,
            "test_loss": val_loss,
            "test_acc": val_acc,
            "down (MiB)": float(total_down / (1024 ** 2)),
            "up (MiB)": float(total_up / (1024 ** 2)),
            "total_time": timer.total_time,
        }
        for logger in loggers:
            logger.append(row)
        if writer is not None:
            for name, value in row.items():
                if name != "epoch":
                    writer.add_scalar(name.split(" ")[0], value, epoch)
        if model.telemetry is not None:
            # drain the one-round-lag metric buffer, then journal the
            # same summary row the stdout table shows
            model.telemetry.flush()
            model.telemetry.journal_event(
                "epoch", **{k.replace(" (MiB)", "_mib"): v
                            for k, v in row.items()})
            # one full epoch compiled everything a steady-state run
            # needs (train round + eval); later compiles are retraces
            # and journal as compile_warning
            model.telemetry.mark_steady_state()

        if cfg.checkpoint_every and epoch % cfg.checkpoint_every == 0:
            # atomic rotated save: keep-last-k round-stamped files + a
            # `latest` manifest, so a preemption at ANY instant leaves
            # a loadable checkpoint for --resume (utils/checkpoint)
            import time
            t0 = time.monotonic()  # monotonic like the sibling sites
            # queued span-boundary writes (--pipeline) must land
            # before this synchronous save rotates the manifest
            model.drain_persistence()
            with TRACE.span("checkpoint", round=int(rounds_done)):
                path = save_rotating(
                    _ckpt_path(cfg), model.server, model.clients,
                    keep_last=cfg.keep_checkpoints,
                    max_age_hours=cfg.ckpt_max_age_hours,
                    scheduler_step=lr_scheduler.step_count,
                    accountant=model.accountant,
                    prev_change_words=model._prev_change_words,
                    fingerprint=model.checkpoint_fingerprint,
                    throughput=model.throughput.state_dict(),
                    scheduler=model.scheduler_state(),
                    sampler=model.sampler_state(),
                    async_admit=model.async_admit_state(),
                    client_rows=model.client_rows_payload())
            if model.telemetry is not None:
                model.telemetry.journal_event(
                    "checkpoint", path=path,
                    seconds=round(time.monotonic() - t0, 3))
            if mh.is_coordinator():
                print(f"checkpointed to {path}")

    return True


def _now() -> float:
    # monotonic, not wall clock: every consumer subtracts two _now()
    # values to form a duration (step timing), and a wall-clock delta
    # is not a duration — an NTP step mid-epoch would print negative
    # or wildly wrong step times (graftlint GL011)
    import time
    return time.monotonic()


def _try_tensorboard(log_dir):
    try:
        from torch.utils.tensorboard import SummaryWriter
        return SummaryWriter(log_dir=log_dir)
    # broad by necessity: tensorboard/protobuf version skew raises
    # AttributeError/TypeError, not just ImportError, and no fault-
    # harness code can run inside an import — InjectedFault cannot
    # originate here
    except Exception as e:  # graftlint: disable=GL005 -- optional-dep probe
        print(f"tensorboard unavailable ({e}); continuing without")
        return None


def _ckpt_path(cfg: Config) -> str:
    return os.path.join(cfg.checkpoint_path, cfg.model)


# ---------------- main (reference cv_train.py:289-421) -------------------

def main(argv=None) -> bool:
    enable_persistent_compilation_cache()
    cfg = parse_args(argv=argv)
    if cfg.multihost:
        # must precede every backend touch (jax.device_count below)
        mh.initialize_from_config(cfg)
    if mh.is_coordinator():
        print(cfg)
    timer = Timer()
    np.random.seed(cfg.seed)

    # --test smoke shrink (reference cv_train.py:329-336)
    model_config = {}
    if cfg.do_test:
        model_config["channels"] = {"prep": 1, "layer1": 1,
                                    "layer2": 1, "layer3": 1}
        cfg = cfg.replace(num_cols=10, num_rows=1, k=10)
    if cfg.do_finetune:
        assert cfg.finetuned_from is not None, \
            "--finetuned_from required with --finetune"
    model_config.update(num_classes=num_classes_of_dataset(cfg.dataset_name),
                        do_batchnorm=cfg.do_batchnorm)

    train_loader, val_loader = get_data_loaders(cfg)

    # derive the model's input shape from the actual (transformed)
    # data — 32x32x3 CIFAR, 28x28x1 EMNIST, 224x224x3 ImageNet all
    # route through here (the reference hardwires per-dataset
    # model_config at cv_train.py:345-358)
    x0 = train_loader.dataset.get_client_batch(0, np.array([0]))[0]
    model_config["initial_channels"] = int(x0.shape[-1])
    module = models.build_model(cfg.model, **model_config)
    init_x = jnp.zeros((2,) + x0.shape[1:], jnp.float32)
    params = module.init(jax.random.PRNGKey(cfg.seed), init_x)

    # finetune: transfer the old body, keep the fresh head, and freeze
    # the transferred leaves by zeroing their per-parameter LR
    # (reference freezes with requires_grad=False + head-only param
    # groups, cv_train.py:377-384)
    lr_scale_vec = None
    if cfg.do_finetune:
        # resolve like --resume does (manifest -> stamped -> fixed
        # name): a preempted pretrain run leaves only rotated
        # checkpoints, and its newest state is still finetunable
        src = latest_checkpoint_path(
            os.path.join(cfg.finetune_path, cfg.model))
        if src is None:
            raise FileNotFoundError(
                f"no checkpoint for model {cfg.model!r} under "
                f"--finetune_path {cfg.finetune_path!r}")
        old_server = load_checkpoint(src).server
        # rebuild the OLD model's param template to unflatten into
        old_cfg_classes = num_classes_of_dataset(cfg.finetuned_from)
        old_module = models.build_model(
            cfg.model, **{**model_config, "num_classes": old_cfg_classes})
        old_params = old_module.init(jax.random.PRNGKey(cfg.seed), init_x)
        from commefficient_tpu.ops.flat import flatten_params
        _, old_unravel = flatten_params(old_params)
        params, frozen_mask = transfer_for_finetune(
            old_unravel(old_server.ps_weights), params)
        lr_scale_vec = _mask_to_lr_scales(params, frozen_mask)

    # Fixup nets: biases and scalar scales train at 0.1x LR via a
    # per-parameter scale vector (reference cv_train.py:366-376 builds
    # param groups with lr 0.1/0.1/1)
    if cfg.model.startswith("Fixup"):
        if mh.is_coordinator():
            print("using fixup learning rates")
        lr_scale_vec = _fixup_lr_scales(params)

    compute_loss = make_compute_loss(module)
    model = FedModel(None, compute_loss, cfg, params=params,
                     num_clients=train_loader.dataset.num_clients,
                     lr_scale_vec=lr_scale_vec)
    opt = FedOptimizer(model)

    # round scheduler (commefficient_tpu/scheduler): policy-driven
    # participant sampling + deadline-driven rounds over the model's
    # own throughput tracker. Attached BEFORE --resume so a
    # checkpoint's sched_* counters restore into this instance; the
    # uniform/no-deadline default is bit-identical to a scheduler-free
    # build.
    from commefficient_tpu.scheduler import attach_round_scheduler
    attach_round_scheduler(model, train_loader)

    # coordinator-broadcast control plane (ISSUE 12): attach the
    # configured plan transport — "collective" wires the production
    # one-to-all host broadcast onto the scheduler above, "emulated"
    # replaces it with the in-process N-controller harness (the CI
    # fault surface). Attached BEFORE --resume like the scheduler, so
    # restored sched_* counters land in every controller replica.
    from commefficient_tpu.parallel.plantransport import (
        attach_config_transport,
    )
    attach_config_transport(model, train_loader, cfg)

    if mh.is_multihost():
        # per-process batch feeding — or, on non-contiguous layouts,
        # the globalize() fallback (one shared implementation:
        # multihost.apply_feed_slices)
        mh.apply_feed_slices(model, train_loader, val_loader,
                             cfg.num_workers, val_loader.num_shards)

    sched_step = 0
    ckpt_fallbacks = []
    if cfg.resume:
        # auto-resume-from-latest, corruption-tolerant (ISSUE 12
        # satellite): integrity-check the newest rotated checkpoint
        # against the manifest's per-array checksums and FALL BACK to
        # the previous keep-last-k rotation when it is corrupt or
        # truncated, instead of crashing mid-resume; each skipped file
        # is journaled as a loud `checkpoint_fallback` event once the
        # telemetry session exists. Fingerprint-validated so a wrong
        # checkpoint dir still fails with the offending field named.
        loaded = load_resilient(
            _ckpt_path(cfg),
            expect_fingerprint=model.checkpoint_fingerprint,
            on_fallback=lambda p, why: ckpt_fallbacks.append((p, why)))
        if loaded is not None:
            ck_file, ckpt = loaded
            sched_step = model.load_state(ckpt)
            if mh.is_coordinator():
                print(f"resumed from {ck_file} at round "
                      f"{int(ckpt.server.round_idx)}")
        if model.plan_transport is not None and cfg.journal_path:
            # deterministic restart (ISSUE 12): load the pre-crash
            # run's write-ahead plan stream — replayed rounds must
            # recompute the identical install digests, or the resume
            # fails loud instead of silently rewriting history
            model.load_plan_stream(cfg.journal_path)

    # LR schedule (reference cv_train.py:392-404; cifar10-fast default
    # knots [0, pivot, num_epochs] -> [0, lr_scale, 0])
    lr_scale = cfg.lr_scale if cfg.lr_scale is not None else 0.4
    schedule = PiecewiseLinear([0, cfg.pivot_epoch, cfg.num_epochs],
                               [0, lr_scale, 0])
    spe = train_loader.steps_per_epoch
    lr_scheduler = LambdaLR(opt, lr_lambda=lambda step: schedule(step / spe))
    lr_scheduler.load_state_dict({"step_count": sched_step})

    coord = mh.is_coordinator()
    # only the coordinator creates a run dir
    log_dir = make_logdir(cfg) if coord else ""
    from commefficient_tpu.telemetry import attach_run_telemetry
    tele = attach_run_telemetry(model, cfg, log_dir, coord,
                                driver="cv_train",
                                materialize=mh.gather_host)
    if tele is not None:
        # resume-time integrity fallbacks, journaled now that the
        # session exists (the resume ran before telemetry attach)
        for p, why in ckpt_fallbacks:
            tele.journal_event("checkpoint_fallback", path=p,
                               error=why[:200])
    if coord:
        print(f"Finished initializing in {timer():.2f} seconds")

    ok = False
    try:
        from commefficient_tpu.telemetry import NumericTripError
        trips = 0
        while True:
            try:
                ok = train(model, opt, lr_scheduler, train_loader,
                           val_loader, cfg,
                           loggers=(TableLogger(),) if coord else (),
                           timer=timer, log_dir=log_dir)
                break
            except NumericTripError as trip:
                # finite-frontier auto-rollback (ISSUE 16): the trip
                # is already journaled durable; walk back to the
                # newest finite checkpoint and replay with screening
                # forced on. Bounded — exhausting the budget (or
                # having no finite checkpoint) fails loud.
                trips += 1
                if trips > cfg.max_numeric_rollbacks:
                    raise
                sched_step = numeric_rollback(
                    model, _ckpt_path(cfg), cfg, tele, trip)
                if sched_step is None:
                    raise
                lr_scheduler.load_state_dict(
                    {"step_count": sched_step})
        model.finalize()

        if cfg.do_checkpoint:
            # collective (gathers sharded client state); coordinator
            # writes stamped + manifest (what --resume prefers) AND the
            # fixed-name artifact the finetune path loads, in one gather
            model.drain_persistence()
            path = save_final(
                _ckpt_path(cfg), model.server, model.clients,
                keep_last=cfg.keep_checkpoints,
                max_age_hours=cfg.ckpt_max_age_hours,
                scheduler_step=lr_scheduler.step_count,
                accountant=model.accountant,
                prev_change_words=model._prev_change_words,
                fingerprint=model.checkpoint_fingerprint,
                throughput=model.throughput.state_dict(),
                scheduler=model.scheduler_state(),
                sampler=model.sampler_state(),
                async_admit=model.async_admit_state(),
                client_rows=model.client_rows_payload())
            if coord:
                print(f"saved checkpoint to {path}")
    finally:
        # close even when training raises (an InjectedFault drill, a
        # NaN abort, a real crash): the session must detach its global
        # compile listener and stop any live profiler capture, or the
        # next in-process run inherits both. The persistence writer
        # drains FIRST (--pipeline): a queued span checkpoint flushes
        # at a crash exactly like at a clean shutdown.
        try:
            model.close_persistence()
        finally:
            if tele is not None:
                tele.close(ok=bool(ok))
    return ok




def _mask_to_lr_scales(params, frozen_mask) -> np.ndarray:
    """Flat per-parameter LR-scale vector: 0.0 where frozen_mask marks
    a leaf as transferred/frozen, 1.0 elsewhere."""
    import jax.tree_util as jtu

    segs = []
    for leaf, frozen in zip(jtu.tree_leaves(params),
                            jtu.tree_leaves(frozen_mask)):
        scale = 0.0 if float(frozen) else 1.0
        segs.append(np.full(int(np.prod(leaf.shape)), scale, np.float32))
    return np.concatenate(segs)


def _fixup_lr_scales(params) -> np.ndarray:
    """Flat per-parameter LR-scale vector: 0.1 for bias/scale scalars,
    1.0 elsewhere (reference param groups, cv_train.py:366-376)."""
    import jax.tree_util as jtu

    leaves = jtu.tree_flatten_with_path(params)[0]
    segs = []
    for path, leaf in leaves:
        names = "/".join(str(p) for p in path).lower()
        scale = 0.1 if ("bias" in names or "scale" in names
                        or "mul" in names or "add" in names) else 1.0
        segs.append(np.full(int(np.prod(leaf.shape)), scale, np.float32))
    return np.concatenate(segs)


def cli() -> None:
    """Console entry point (`cv-train`, pyproject.toml)."""
    raise SystemExit(0 if main() else 1)


if __name__ == "__main__":
    cli()

"""The cv job, built from `commefficient_tpu.training.cv_train`'s own
pieces and driven by its per-round loop.

`build` follows `cv_train.main` step for step (loaders, model,
FedModel, FedOptimizer, round scheduler, LR schedule, telemetry) and
leaves out what a benchmark window has no use for (resume, finetune,
checkpoints, the table logger). The weights are the benchmark's, made
from the seed by the configuration's reference module and handed to
FedModel as `params`. `rounds` is the unscanned branch of
`cv_train.train` (and of `gpt2_train.train_gpt2`, which has the same
shape) as a generator, one round per `next`: LR step,
`FedModel.__call__`, `FedOptimizer.step`, byte totals, and the loss of
the round before materialised with one round's lag; at the end of an
epoch's stream the pending loss is read and the next
`FedLoader.epoch()` begins, as `train()` does, without its eval.
"""
from __future__ import annotations

import os
import time
from typing import NamedTuple

import numpy as np


class RoundOut(NamedTuple):
    batch: tuple          # (client_ids, data, mask) as fed
    lr: float
    outputs: list         # what FedModel.__call__ returned
    stage_s: float        # host seconds pulling the batch from the loader
    api_s: float          # host seconds inside FedModel.__call__
    upload_bytes: float
    valid_examples: float


def common_argv(config: dict, traffic: dict, seed: int, data_dir: str,
                journal: str, bf16: bool, trace: bool) -> list:
    """The arguments both drivers take, from the traffic file."""
    t = traffic
    argv = ["--dataset_name", config["dataset_name"],
            "--dataset_dir", data_dir,
            "--mode", t["mode"], "--error_type", t["error_type"],
            "--virtual_momentum", repr(t["virtual_momentum"]),
            "--local_momentum", repr(t["local_momentum"]),
            "--weight_decay", repr(config["weight_decay"]),
            "--num_workers", str(t["num_workers"]),
            "--local_batch_size", str(t["local_batch_size"]),
            "--num_clients", str(t["num_clients"]),
            "--k", str(t["k"]),
            "--num_epochs", repr(t["num_epochs"]),
            "--lr_scale", repr(t["lr_scale"]),
            "--seed", str(seed % (2 ** 32)),
            "--journal_path", journal]
    if t["mode"] == "sketch":
        argv += ["--num_rows", str(t["num_rows"]),
                 "--num_cols", str(t["num_cols"])]
    if t.get("iid"):
        argv.append("--iid")
    if bf16:
        argv.append("--bf16")
    if trace:
        argv.append("--trace")
    return argv + list(t.get("extra_argv", ()))


def argv_for(config: dict, traffic: dict, seed: int, data_dir: str,
             journal: str, bf16: bool, trace: bool) -> list:
    return (["--model", config["model"],
             "--pivot_epoch", repr(traffic["pivot_epoch"])]
            + common_argv(config, traffic, seed, data_dir, journal,
                          bf16, trace))


def build(config: dict, traffic: dict, ref_module, seed: int,
          data_dir: str, journal: str, bf16: bool = False,
          trace: bool = False):
    import jax
    from commefficient_tpu import models
    from commefficient_tpu.config import num_classes_of_dataset, parse_args
    from commefficient_tpu.federated.api import FedModel, FedOptimizer
    from commefficient_tpu.scheduler import attach_round_scheduler
    from commefficient_tpu.telemetry import attach_run_telemetry
    from commefficient_tpu.training import cv_train
    from commefficient_tpu.utils.schedules import LambdaLR, PiecewiseLinear

    cfg = parse_args(argv=argv_for(config, traffic, seed, data_dir,
                                   journal, bf16, trace))
    np.random.seed(cfg.seed)
    train_loader, _ = cv_train.get_data_loaders(cfg)
    x0 = train_loader.dataset.get_client_batch(0, np.array([0]))[0]
    model_config = dict(
        num_classes=num_classes_of_dataset(cfg.dataset_name),
        do_batchnorm=cfg.do_batchnorm,
        initial_channels=int(x0.shape[-1]))
    if config["channels"] != dict(models.resnet9.DEFAULT_CHANNELS):
        # a test-only configuration at narrower widths
        model_config["channels"] = dict(config["channels"])
    module = models.build_model(cfg.model, **model_config)
    params = ref_module.init_params(config, seed)
    model = FedModel(None, cv_train.make_compute_loss(module), cfg,
                     params=params,
                     num_clients=train_loader.dataset.num_clients)
    if model.cfg.grad_size != config["grad_size"]:
        raise SystemExit(
            f"fedbench: the program built D={model.cfg.grad_size}, the "
            f"configuration states {config['grad_size']}")
    opt = FedOptimizer(model)
    attach_round_scheduler(model, train_loader)
    lr_scale = cfg.lr_scale if cfg.lr_scale is not None else 0.4
    schedule = PiecewiseLinear([0, cfg.pivot_epoch, cfg.num_epochs],
                               [0, lr_scale, 0])
    spe = train_loader.steps_per_epoch
    lr_scheduler = LambdaLR(opt, lr_lambda=lambda s: schedule(s / spe))
    tele = attach_run_telemetry(model, cfg, os.path.dirname(journal),
                                True, driver="cv_train")
    return Built(model, opt, lr_scheduler, train_loader, tele, params)


class Built(NamedTuple):
    model: object
    opt: object
    lr_scheduler: object
    loader: object
    tele: object
    params: object


def rounds(job: Built, tamper=None, clock=time.perf_counter):
    """The drivers' unscanned loop (cv_train.train and
    gpt2_train.train_gpt2 share its shape), one round per `next`.
    `tamper(batch)` (fault tests only) alters what the program is
    fed; the RoundOut carries the batch as the loader made it."""
    from jax.profiler import TraceAnnotation

    model, opt, lr_scheduler, loader = job[:4]
    rounds_done = 0

    def emit(p) -> None:
        *metrics, at = p
        loss = [float(np.mean(np.asarray(m))) for m in metrics][0]
        if np.isnan(loss) or loss > model.cfg.nan_threshold:
            raise SystemExit(f"fedbench: loss {loss} at round {at}")

    while True:
        if model.scheduler is not None:
            model.scheduler.begin_epoch(rounds_done)
        stream = iter(loader.epoch())
        pending = None
        while True:
            t0 = clock()
            try:
                with TraceAnnotation("fedbench:stage"):
                    client_ids, data, mask = next(stream)
            except StopIteration:
                break
            t1 = clock()
            fed = (client_ids, data, mask)
            if tamper is not None:
                fed = tamper(fed)
            lr_scheduler.step()
            with TraceAnnotation("fedbench:api"):
                *metrics, d, u = model(fed)
            opt.step()
            t2 = clock()
            float(np.sum(d))
            up = float(np.sum(u))
            if pending is not None:
                emit(pending)
            pending = (*metrics, rounds_done)
            rounds_done += 1
            yield RoundOut((client_ids, data, mask),
                           float(opt.param_groups[0]["lr"]),
                           [*metrics, d, u], t1 - t0, t2 - t1, up,
                           float(mask.sum()))
        if pending is not None:
            emit(pending)


def state_after_first(job: Built, batch) -> dict:
    """Named rows of the optimizer state, copied to the host: the
    server's momentum table in sketch mode, the cohort's velocity and
    error rows under local_topk."""
    import jax

    model = job.model
    cfg = model.cfg
    out = {}
    if cfg.mode == "sketch":
        V = np.asarray(jax.device_get(model.server.Vvelocity))
        for j in range(V.shape[0]):
            out[f"momentum[{j}]"] = V[j]
    elif cfg.mode == "local_topk":
        ids = np.asarray(batch[0])
        if cfg.local_momentum > 0:
            rows = np.asarray(jax.device_get(model.clients.velocities[ids]))
            for i in range(len(ids)):
                out[f"velocity[{i}]"] = rows[i]
        if cfg.error_type == "local":
            rows = np.asarray(jax.device_get(model.clients.errors[ids]))
            for i in range(len(ids)):
                out[f"error[{i}]"] = rows[i]
    else:
        out["momentum"] = np.asarray(jax.device_get(model.server.Vvelocity))
    return out


def weights(job: Built) -> np.ndarray:
    import jax
    return np.asarray(jax.device_get(job.model.server.ps_weights))


def sync(job: Built) -> None:
    import jax
    jax.block_until_ready(job.model.server)


def close(job: Built, ok: bool) -> None:
    try:
        job.model.close_persistence()
    finally:
        if job.tele is not None:
            job.tele.close(ok=bool(ok))

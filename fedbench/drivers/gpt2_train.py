"""The gpt2 job, built from `commefficient_tpu.training.gpt2_train`'s
own pieces and driven by its per-round loop.

`build` follows `gpt2_train.main`: tokenizer, loaders, model, the
double-heads loss, FedModel, FedOptimizer, round scheduler, LR
schedule, telemetry. The module comes from `models.gpt2.build_gpt2`
at the sizes `build_model_and_params` would give it from scratch. The tokenizer is `HashTokenizer(vocab_size)`, so
the program builds the model at the published vocabulary (plus the
five PersonaChat special tokens) with no file to fetch; the weights
are the benchmark's, made from the seed by the configuration's
reference module. `rounds` is cv_train's: the two drivers' unscanned
loops have one shape.
"""
from __future__ import annotations

import os

import numpy as np

from fedbench.drivers.cv_train import (  # noqa: F401  (same contract)
    Built, RoundOut, close, common_argv, rounds, state_after_first, sync,
    weights,
)


def argv_for(config: dict, traffic: dict, seed: int, data_dir: str,
             journal: str, bf16: bool, trace: bool) -> list:
    # a directory with nothing in it: no checkpoint and no tokenizer
    # is looked up by name
    nothing = os.path.join(os.path.dirname(journal), "no_checkpoint")
    os.makedirs(nothing, exist_ok=True)
    return (["--model_checkpoint", nothing,
             "--num_candidates", str(config["num_candidates"]),
             "--max_history", str(config["max_history"]),
             "--lm_coef", repr(config["lm_coef"]),
             "--mc_coef", repr(config["mc_coef"])]
            + common_argv(config, traffic, seed, data_dir, journal,
                          bf16, trace))


def build(config: dict, traffic: dict, ref_module, seed: int,
          data_dir: str, journal: str, bf16: bool = False,
          trace: bool = False):
    from commefficient_tpu.config import parse_args
    from commefficient_tpu.data.persona import HashTokenizer
    from commefficient_tpu.federated.api import FedModel, FedOptimizer
    from commefficient_tpu.scheduler import attach_round_scheduler
    from commefficient_tpu.telemetry import attach_run_telemetry
    from commefficient_tpu.training import gpt2_train
    from commefficient_tpu.utils.schedules import LambdaLR, PiecewiseLinear

    cfg = parse_args(default_lr=4e-2, argv=argv_for(
        config, traffic, seed, data_dir, journal, bf16, trace))
    np.random.seed(cfg.seed)
    tokenizer = HashTokenizer(config["vocab_size"])
    train_loader, val_loader = gpt2_train.get_data_loaders(cfg, tokenizer)
    seq_len = max(train_loader.dataset.seq_len,
                  val_loader.dataset.seq_len)
    expect = traffic["corpus"].get("max_tokens")
    if expect and train_loader.dataset.seq_len != expect:
        raise SystemExit(
            f"fedbench: the corpus pads to "
            f"{train_loader.dataset.seq_len} tokens, the traffic file "
            f"states {expect}")
    # gpt2_train.build_model_and_params would build this same module,
    # after importing transformers, torch and tensorflow to look for a
    # checkpoint that is not there (45-55 s a process) and after a
    # random init of its own that nothing uses (PERF.md, section 7)
    from commefficient_tpu.models.gpt2 import build_gpt2
    module = build_gpt2(
        config["model"], vocab_size=len(tokenizer),
        n_positions=max(config["n_positions"], seq_len),
        n_embd=config["n_embd"], n_layer=config["n_layer"],
        n_head=config["n_head"], remat=cfg.do_remat)
    params = ref_module.init_params(config, seed)
    model = FedModel(None, gpt2_train.make_compute_loss_train(module, cfg),
                     cfg, loss_val=gpt2_train.make_compute_loss_val(module),
                     params=params,
                     num_clients=train_loader.dataset.num_clients)
    if model.cfg.grad_size != config["grad_size"]:
        raise SystemExit(
            f"fedbench: the program built D={model.cfg.grad_size}, the "
            f"configuration states {config['grad_size']}")
    opt = FedOptimizer(model)
    attach_round_scheduler(model, train_loader)
    spe = train_loader.steps_per_epoch
    schedule = PiecewiseLinear([0, cfg.num_epochs * spe],
                               [cfg.lr_scale, 0.0])
    lr_scheduler = LambdaLR(opt, lr_lambda=schedule)
    tele = attach_run_telemetry(model, cfg, os.path.dirname(journal),
                                True, driver="gpt2_train")
    return Built(model, opt, lr_scheduler, train_loader, tele, params)

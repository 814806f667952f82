"""The SmallThinker job, built from `commefficient_tpu.training.
gpt2_train`'s own pieces and driven by its per-round loop.

`build` follows `gpt2_train.main` for `--model smallthinker`: the
model's `config.json` is written where `--model_checkpoint` points and
read back by the driver's own `smallthinker_config`, the ids come from
`HashTokenizer(vocab_size)`, the loaders are the driver's, the loss is
`build_smallthinker`'s language-model loss, then FedModel,
FedOptimizer, round scheduler, LR schedule and telemetry as for GPT2.
The weights are the benchmark's, made by the configuration's reference
module from the configuration's `weights_seed` (one model under every
`--seed`, which then draws the traffic alone: since the expert layer
carries only the held picks' rows, a round's time follows the routing,
and a random router's routing follows its weights), or from `--seed`
where the configuration states none.

At D = 6.6e8 a copy of the model is 2.6 GB, so this driver keeps none
it does not need: the tree of weights is dropped once FedModel has
flattened it (`Built.params` holds shapes, which is all the harness
reads of it), and the host copies the harness asks for (the weights
before the first and after the third round, the momentum after the
first) come off the device in pieces (`reference.to_host`).

`rounds` is cv_train's loop with the two byte totals read one round
late, like the loss.
"""
from __future__ import annotations

import json
import os
import time

import numpy as np

from fedbench.drivers.cv_train import (  # noqa: F401  (same contract)
    Built, RoundOut, close, common_argv, sync,
)
from fedbench.reference import to_host

# keys of the configuration file that are the model's public config.json
# (plus the two that say which experts of how many are held here)
PUBLISHED = (
    "head_dim", "hidden_size", "max_position_embeddings", "model_name",
    "moe_ffn_hidden_size", "moe_num_active_primary_experts",
    "moe_num_primary_experts", "moe_primary_router_apply_softmax",
    "norm_topk_prob", "num_attention_heads", "num_hidden_layers",
    "num_key_value_heads", "rms_norm_eps", "rope_layout", "rope_scaling",
    "rope_theta", "sliding_window_layout", "sliding_window_size",
    "tie_word_embeddings", "vocab_size", "router_width", "held_experts")

def argv_for(config: dict, traffic: dict, seed: int, data_dir: str,
             journal: str, bf16: bool, trace: bool) -> list:
    checkpoint = os.path.join(os.path.dirname(journal), "model")
    os.makedirs(checkpoint, exist_ok=True)
    with open(os.path.join(checkpoint, "config.json"), "w") as f:
        json.dump({k: config[k] for k in PUBLISHED}, f)
    argv = ["--model", config["model"],
            "--model_checkpoint", checkpoint,
            "--num_candidates", str(config["num_candidates"]),
            "--max_history", str(config["max_history"])]
    if config.get("remat"):
        argv.append("--remat")
    return argv + common_argv(config, traffic, seed, data_dir, journal,
                              bf16, trace)


def build(config: dict, traffic: dict, ref_module, seed: int,
          data_dir: str, journal: str, bf16: bool = False,
          trace: bool = False):
    import jax
    from commefficient_tpu.config import parse_args
    from commefficient_tpu.data.persona import HashTokenizer
    from commefficient_tpu.federated.api import FedModel, FedOptimizer
    from commefficient_tpu.scheduler import attach_round_scheduler
    from commefficient_tpu.telemetry import attach_run_telemetry
    from commefficient_tpu.training import gpt2_train
    from commefficient_tpu.utils.schedules import LambdaLR, PiecewiseLinear

    if not hasattr(gpt2_train, "smallthinker_config"):
        raise SystemExit("fedbench: this program has no SmallThinker "
                         "model (--model smallthinker)")
    cfg = parse_args(default_lr=4e-2, argv=argv_for(
        config, traffic, seed, data_dir, journal, bf16, trace))
    np.random.seed(cfg.seed)
    mcfg = gpt2_train.smallthinker_config(cfg)
    tokenizer = HashTokenizer(mcfg.vocab_size)
    train_loader, _ = gpt2_train.get_data_loaders(cfg, tokenizer)
    expect = traffic["corpus"].get("max_tokens")
    if expect and train_loader.dataset.seq_len != expect:
        raise SystemExit(
            f"fedbench: the corpus pads to "
            f"{train_loader.dataset.seq_len} tokens, the traffic file "
            f"states {expect}")
    params = ref_module.init_params(config,
                                    config.get("weights_seed", seed))
    shapes = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), params)
    cfg, loss_train, loss_val, params = gpt2_train.build_smallthinker(
        cfg, mcfg, tokenizer, params=params)
    model = FedModel(None, loss_train, cfg, loss_val=loss_val,
                     params=params,
                     num_clients=train_loader.dataset.num_clients)
    del params
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
    return Built(model, opt, lr_scheduler, train_loader, tele, shapes)


def rounds(job: Built, tamper=None, clock=time.perf_counter):
    """`fedbench.drivers.cv_train.rounds`, with the round's download
    and upload totals materialised one round late beside its loss."""
    from jax.profiler import TraceAnnotation

    model, opt, lr_scheduler, loader = job[:4]
    rounds_done = 0

    def emit(p) -> None:
        loss, down, up, at = p
        loss = float(np.mean(np.asarray(loss)))
        float(np.sum(down)), float(np.sum(up))
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
            if pending is not None:
                emit(pending)
            pending = (metrics[0], d, u, rounds_done)
            rounds_done += 1
            yield RoundOut((client_ids, data, mask),
                           float(opt.param_groups[0]["lr"]),
                           [*metrics, d, u], t1 - t0, t2 - t1,
                           float(np.sum(u)), float(mask.sum()))
        if pending is not None:
            emit(pending)


def state_after_first(job: Built, batch) -> dict:
    """The server's momentum after the first round, copied to the
    host (the cell's mode is `uncompressed`)."""
    return {"momentum": to_host(job.model.server.Vvelocity)}


def weights(job: Built) -> np.ndarray:
    return to_host(job.model.server.ps_weights)

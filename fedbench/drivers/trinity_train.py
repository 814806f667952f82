"""The Trinity (AFMoE) job, built from `commefficient_tpu.training.
gpt2_train`'s own pieces, as the SmallThinker driver builds its job,
and driven by the same loop (`rounds`, `state_after_first` and
`weights` are `smallthinker_train`'s).

`build` follows `gpt2_train.main` for `--model trinity`: the model's
`config.json` (the published keys, plus the three that say which
layers and which experts of how many are held here) is written where
`--model_checkpoint` points and read back by the driver's own
`decoder_config`; ids from `HashTokenizer(vocab_size)`, the driver's
loaders, `build_decoder`'s language-model loss, then FedModel,
FedOptimizer, round scheduler, LR schedule and telemetry. The weights
are the benchmark's, made by the configuration's reference module
from its `weights_seed`.

A program without the model (an older commit) is refused when this
file is loaded, before the device or the corpus is touched.
"""
from __future__ import annotations

import importlib.util
import json
import os

import numpy as np

from fedbench.drivers.cv_train import (  # noqa: F401  (same contract)
    Built, close, common_argv, sync,
)
from fedbench.drivers.smallthinker_train import (  # noqa: F401
    rounds, state_after_first, weights,
)

if importlib.util.find_spec("commefficient_tpu.models.afmoe") is None:
    raise SystemExit("fedbench: this program has no Trinity (AFMoE) "
                     "model (--model trinity)")

# keys of the configuration file that are the model's public config.json
# (plus the three that say which layers and experts are held here)
PUBLISHED = (
    "global_attn_every_n_layers", "head_dim", "hidden_act",
    "hidden_size", "intermediate_size", "layer_types",
    "load_balance_coeff", "max_position_embeddings", "model_type",
    "moe_intermediate_size", "mup_enabled", "n_group",
    "num_attention_heads", "num_dense_layers", "num_expert_groups",
    "num_experts", "num_experts_per_tok", "num_hidden_layers",
    "num_key_value_heads", "num_limited_groups", "num_shared_experts",
    "rms_norm_eps", "rope_scaling", "rope_theta", "route_norm",
    "route_scale", "score_func", "sliding_window", "tie_word_embeddings",
    "topk_group", "use_grouped_mm", "vocab_size",
    "router_width", "held_experts", "first_layer")


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

    cfg = parse_args(default_lr=4e-2, argv=argv_for(
        config, traffic, seed, data_dir, journal, bf16, trace))
    np.random.seed(cfg.seed)
    mcfg = gpt2_train.decoder_config(cfg)
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
    cfg, loss_train, loss_val, params = gpt2_train.build_decoder(
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

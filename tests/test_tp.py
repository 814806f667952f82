"""Tensor parallelism over the (clients, model) mesh.

The round engine runs manual shard_map over `clients` with the `model`
axis left to GSPMD (round.py axis_names), steered by the Megatron-style
constraints in parallel/tp.py. Correctness bar: a federated GPT2 round
on the 2-D mesh must produce the SAME weights as the 1-D clients-only
mesh — tensor parallelism is an execution layout, not an algorithm
change."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from commefficient_tpu.config import Config
from commefficient_tpu.federated.api import FedModel, FedOptimizer
from commefficient_tpu.models.gpt2 import GPT2Config, GPT2DoubleHeads
from commefficient_tpu.parallel.mesh import (
    make_client_mesh, make_client_model_mesh,
)
from commefficient_tpu.parallel.tp import GPT2_TP_RULES, tp_loss
from commefficient_tpu.training.gpt2_train import make_compute_loss_train

W, B, C, L = 4, 2, 2, 8


def build(mesh, wrap):
    gcfg = GPT2Config(vocab_size=64, n_positions=L, n_embd=16,
                      n_layer=2, n_head=2)
    module = GPT2DoubleHeads(gcfg)
    key = jax.random.PRNGKey(0)
    x0 = jnp.zeros((1, C, L), jnp.int32)
    params = module.init(key, x0, x0, jnp.zeros((1, C), jnp.int32))
    cfg = Config(mode="uncompressed", error_type="virtual",
                 virtual_momentum=0.9, local_momentum=0.0,
                 weight_decay=0.0, microbatch_size=-1, num_workers=W,
                 num_clients=W, grad_size=1, lm_coef=1.0, mc_coef=1.0)
    loss = make_compute_loss_train(module, cfg)
    if wrap:
        loss = tp_loss(loss, mesh, GPT2_TP_RULES)
    model = FedModel(None, loss, cfg, params=params, mesh=mesh,
                     num_clients=W)
    opt = FedOptimizer(model)
    opt.param_groups[0]["lr"] = 0.1
    return model


def batch(seed=0):
    rng = np.random.RandomState(seed)
    ids = np.arange(W)
    input_ids = rng.randint(0, 64, (W, B, C, L)).astype(np.int32)
    mc_tok = rng.randint(0, L, (W, B, C)).astype(np.int32)
    lm_labels = rng.randint(0, 64, (W, B, C, L)).astype(np.int32)
    mc_labels = rng.randint(0, C, (W, B)).astype(np.int32)
    tt = rng.randint(0, 64, (W, B, C, L)).astype(np.int32)
    mask = np.ones((W, B), np.float32)
    return ids, (input_ids, mc_tok, lm_labels, mc_labels, tt), mask


def test_tp_round_matches_dp_round():
    if len(jax.devices()) < 8:
        pytest.skip("needs the 8-device CPU mesh")
    m1 = make_client_mesh(4)
    m2 = make_client_model_mesh(4, 2)

    dp = build(m1, wrap=False)
    tp = build(m2, wrap=True)
    np.testing.assert_allclose(np.asarray(dp.ps_weights),
                               np.asarray(tp.ps_weights))

    for r in range(2):
        ids, data, mask = batch(seed=r)
        out_dp = dp((ids, data, mask))
        out_tp = tp((ids, data, mask))
        np.testing.assert_allclose(out_dp[0], out_tp[0], rtol=2e-5)

    np.testing.assert_allclose(np.asarray(dp.ps_weights),
                               np.asarray(tp.ps_weights),
                               rtol=2e-4, atol=1e-6)
    # and the TP run actually trained
    assert float(jnp.abs(tp.ps_weights).sum()) > 0


def test_tp_eval_matches_dp_eval():
    if len(jax.devices()) < 8:
        pytest.skip("needs the 8-device CPU mesh")
    dp = build(make_client_mesh(4), wrap=False)
    tp = build(make_client_model_mesh(4, 2), wrap=True)
    _, data, mask = batch(seed=3)
    dp.train(False)
    tp.train(False)
    out_dp = dp((data, mask))
    out_tp = tp((data, mask))
    np.testing.assert_allclose(out_dp[0], out_tp[0], rtol=2e-5)

"""The round-5 performance paths are selected by static gates
(Config.fused_client_backward, ops/flat.py TOPK_THRESHOLD_MIN_D,
ops/sketch.py THRESHOLD_DECODE_MIN_D, CSVec.encode_k_sparse's scatter
bound). These tests pin that each gate is ACTIVE at the BASELINE bench
geometries it was built for — a refactor that silently flips one back
to the slow path (a 31M-element ApproxTopK sort per GPT2 decode, a
4.8M-element table scatter, a [W, D] per-client gradient stack) would
otherwise only show up as a regressed TPU number the next time the
chip is asked. Pure-python/static checks: no device compute.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from commefficient_tpu.config import Config
from commefficient_tpu.federated.server import args2sketch
from commefficient_tpu.ops import flat
from commefficient_tpu.ops.sketch import THRESHOLD_DECODE_MIN_D

@pytest.fixture(autouse=True)
def _no_transfers(sanitize):
    """These gate checks are 'pure-python/static: no device compute' by
    contract (module docstring) — arm the transfer guard over every
    test so a refactor that sneaks device work (and its host<->device
    traffic) into a gate predicate fails here, not on the next chip
    run."""
    with sanitize.forbid_transfers():
        yield


GPT2_D = 123_756_289      # GPT2-small double-heads
LTK_D = 5_252_388         # PreAct ResNet18 / CIFAR100
FLAGSHIP_D = 6_568_640    # ResNet9 / CIFAR10


def gpt2_cfg():
    return Config(
        mode="sketch", k=max(GPT2_D // 130, 1000), num_rows=5,
        num_cols=max(GPT2_D // 13, 10_000), num_blocks=20,
        error_type="virtual", virtual_momentum=0.9, local_momentum=0.0,
        weight_decay=0.0, microbatch_size=-1, num_workers=4,
        num_clients=40, grad_size=GPT2_D).validate()


def test_gpt2_bench_geometry_takes_every_fast_path():
    cfg = gpt2_cfg()
    assert cfg.defer_sketch_encode
    assert cfg.fused_client_backward
    sk = args2sketch(cfg)
    # threshold decode active AND the materialized path it needs
    assert sk._threshold_decode
    # the re-encode of the ~952k-sparse update must take the dense
    # route on TPU-class backends (scatter bound crossed)
    assert sk.r * cfg.k > 1_000_000


def test_local_topk_bench_geometry_takes_threshold_route():
    cfg = Config(
        mode="local_topk", error_type="local", local_momentum=0.9,
        virtual_momentum=0.0, k=max(LTK_D // 130, 500),
        weight_decay=5e-4, microbatch_size=-1, num_workers=8,
        num_clients=100, grad_size=LTK_D).validate()
    # per-client error feedback state means the fused backward must
    # NOT engage (transmit is nonlinear in the gradient)...
    assert not cfg.fused_client_backward
    # ...but the per-client selection is above the threshold gate
    assert LTK_D > flat.TOPK_THRESHOLD_MIN_D


def test_flagship_geometry_keeps_exact_k_semantics():
    # config #2 (and every golden test) stays on exact index top-k:
    # both gates must be ABOVE the flagship size
    assert FLAGSHIP_D < THRESHOLD_DECODE_MIN_D
    cfg = Config(
        mode="sketch", k=50_000, num_rows=5, num_cols=500_000,
        num_blocks=20, error_type="virtual", virtual_momentum=0.9,
        local_momentum=0.0, microbatch_size=-1, num_workers=8,
        num_clients=80, grad_size=FLAGSHIP_D).validate()
    assert not args2sketch(cfg)._threshold_decode
    # the flagship round benefits from the fused backward though
    assert cfg.fused_client_backward


def test_fused_gate_rejects_every_per_client_nonlinearity():
    base = dict(mode="sketch", k=1000, num_rows=5, num_cols=10_000,
                num_blocks=20, error_type="virtual",
                virtual_momentum=0.9, local_momentum=0.0,
                microbatch_size=-1, num_workers=4, num_clients=40,
                grad_size=100_000)
    assert Config(**base).validate().fused_client_backward
    for patch in (dict(mode="local_topk", error_type="local"),
                  dict(mode="fedavg", error_type="none",
                       virtual_momentum=0.0, local_batch_size=-1),
                  dict(microbatch_size=8),
                  dict(do_dp=True, dp_mode="worker"),
                  dict(mode="uncompressed", error_type="none",
                       max_grad_norm=1.0)):
        cfg = Config(**{**base, **patch}).validate()
        assert not cfg.fused_client_backward, patch

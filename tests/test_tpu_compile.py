"""The chip's compiler, asked without the chip.

The TPU compiler installed beside JAX compiles for a chip that is
described, not attached (`jax.experimental.topologies`), so what
Mosaic refuses is found here at no chip time. Kept to one file, the
topology described inside a module-scoped fixture (never at import:
every xdist worker imports this file, and only the worker that runs
it may load the TPU library), no child process, the persistent
compile cache off (an entry written for a described chip cannot be
read back without one).

Cases: the flash-attention forward, the one Pallas kernel on the main
path (models/gpt2.py takes it for every L >= 256 on a TPU), at
GPT2-small head shapes; and the three sketch kernels behind
`kernel_backend="pallas"` at the flagship 5 x 500,000 table with
D=6,568,640 — which Mosaic has never accepted. Those are strict
xfails carrying the compiler's message: the day a re-tiling gets one
through, its test fails until the marker goes.

A compile that passes is not a chip run: chip_smoke.py is.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from commefficient_tpu.ops import attention
from commefficient_tpu.ops.kernels import sketch_pallas
from commefficient_tpu.ops.sketch import CSVec

FLAGSHIP = dict(d=6_568_640, c=500_000, r=5, num_blocks=20)


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means no compiler
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, *shapes, sharding):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding)
            for s, dt in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("L", [256, 1024])
def test_flash_forward_compiles(one_chip, L, dtype):
    shape = ((4, 12, L, 64), dtype)
    block = attention.DEFAULT_BLOCK
    _compile(lambda q, k, v: attention._flash_fwd_pallas(
        q, k, v, 0.125, block, block), shape, shape, shape,
        sharding=one_chip)


def _tpu_sketch(monkeypatch):
    # the kernels ask the backend at trace time whether to interpret;
    # the described chip is not the backend, so steer them here
    monkeypatch.setattr(sketch_pallas, "_interpret", lambda: False)
    return CSVec(backend="pallas", **FLAGSHIP)


_BLOCK_REFUSAL = (
    "Mosaic refuses the (1, c) blocks of [B, c] / [r, c]: the last two "
    "dimensions of a block must be divisible by 8 and 128 or equal the "
    "array's; behind that wall, pltpu.roll of a [1, 500000] row is an "
    "unaligned tpu.dynamic_rotate, and at an aligned c a one-row block "
    "pads to eight sublanes and is double buffered past the 16 MiB "
    "scoped VMEM limit. Needs a re-tiling (ROADMAP D1/S6).")


@pytest.mark.xfail(strict=True, reason=_BLOCK_REFUSAL)
def test_pallas_encode_compiles_at_flagship(one_chip, monkeypatch):
    sk = _tpu_sketch(monkeypatch)
    _compile(lambda v: sketch_pallas.pallas_encode(sk, v),
             ((sk.d,), jnp.float32), sharding=one_chip)


@pytest.mark.xfail(strict=True, reason=_BLOCK_REFUSAL)
def test_pallas_estimate_all_compiles_at_flagship(one_chip, monkeypatch):
    sk = _tpu_sketch(monkeypatch)
    _compile(lambda t: sketch_pallas.pallas_estimate_all(sk, t),
             ((sk.r, sk.c), jnp.float32), sharding=one_chip)


@pytest.mark.xfail(strict=True, reason=_BLOCK_REFUSAL)
def test_pallas_threshold_decode_compiles_at_flagship(one_chip,
                                                      monkeypatch):
    sk = _tpu_sketch(monkeypatch)
    _compile(lambda t: sketch_pallas.pallas_threshold_decode(sk, t, 50_000),
             ((sk.r, sk.c), jnp.float32), sharding=one_chip)

"""Quantized sketch-table wire transport (--sketch_table_dtype).

FetchSGD's error-feedback argument (PAPER.md) extends directly from
sketch compression noise to quantization noise: the server's virtual
error accumulator keeps whatever update mass the decode did not
transmit, so rounding the [r, c] client-sum table to bf16 or int8 on
the wire perturbs WHICH mass transmits this round, not whether it
eventually does. Telemetry's `estimate_residual` metric is the gauge:
if quantization makes the channel fall behind the gradient, the
residual fraction rises (telemetry/metrics.py).

Placement: the round engine applies `wire_roundtrip` to each mesh
shard's locally-summed sketch table immediately before the
`lax.psum` (federated/round.py shard_train) — modeling each
client-group's upload being quantized at the sender and dequantized
at the server before aggregation/decode. The psum itself then moves
dequantized f32 (in the single-program SPMD simulation the psum IS
the wire stand-in); the accountant bills the bytes the QUANTIZED
table would occupy (`wire_table_bytes`, Config.upload_bytes), which
is the quantity the ISSUE-6 accounting satellite corrects.

Determinism: quantization is round-to-nearest-even (jnp.round), no
stochastic rounding — a resumed run replays identical tables, which
the crash->resume bit-exactness contract requires. The f32 "wire
dtype" is the identity (the function returns its argument
UNTOUCHED), so the default config's program is bit-identical to a
build without this module.

Pure elementwise jnp by design: XLA already fuses a cast or a
scale/round/clip chain into the surrounding encode/psum.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

# wire dtype -> bytes per table element
TABLE_DTYPES = {"f32": 4, "bf16": 2, "int8": 1}

# int8 symmetric range: +-127 (the -128 code is unused so the scale
# is symmetric and dequantization is a single multiply)
_INT8_MAX = 127.0


def table_elem_bytes(dtype: str) -> int:
    """Bytes per sketch-table element at wire dtype `dtype`."""
    return TABLE_DTYPES[dtype]


def quantize_table(table: jax.Array, dtype: str):
    """Quantize an [r, c] f32 sketch table for the wire.

    Returns (wire_values, scales) — `scales` is None for f32/bf16 and
    the per-row [r, 1] f32 dequantization scale for int8 (symmetric
    per-row absmax / 127; an all-zero row gets scale 1 so dequantize
    is exact zeros).
    """
    if dtype == "f32":
        return table, None
    if dtype == "bf16":
        return table.astype(jnp.bfloat16), None
    if dtype == "int8":
        absmax = jnp.max(jnp.abs(table), axis=1, keepdims=True)
        scale = jnp.where(absmax > 0, absmax / _INT8_MAX, 1.0)
        q = jnp.clip(jnp.round(table / scale), -_INT8_MAX, _INT8_MAX)
        return q.astype(jnp.int8), scale
    raise ValueError(f"unknown sketch table dtype {dtype!r} "
                     f"(choices: {sorted(TABLE_DTYPES)})")


def dequantize_table(wire, scale) -> jax.Array:
    """Inverse of quantize_table back to f32 (exact for f32 input;
    the bf16/int8 round-trips carry the rounding the error feedback
    absorbs)."""
    out = wire.astype(jnp.float32)
    if scale is not None:
        out = out * scale
    return out


def wire_roundtrip(table: jax.Array, dtype: str) -> jax.Array:
    """Quantize + dequantize: the f32 table the server actually sees
    after a `dtype` wire. Identity (the same array object) for f32."""
    if dtype == "f32":
        return table
    return dequantize_table(*quantize_table(table, dtype))


def wire_table_bytes(num_rows: int, num_cols: int, dtype: str) -> int:
    """Bytes one [r, c] sketch table occupies on a `dtype` wire:
    r * c elements at the wire element size, plus the r f32 per-row
    dequantization scales int8 must ship alongside."""
    n = num_rows * num_cols * table_elem_bytes(dtype)
    if dtype == "int8":
        n += 4 * num_rows
    return n

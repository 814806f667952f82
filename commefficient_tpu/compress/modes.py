"""The five classic modes as Compressor plugins (ISSUE 19 migration).

Bit-identity contract: every traced hook here either is the base-class
identity or contains the EXACT code the engine ran inline before the
migration (forward_grad's sketch encode, local_step's local_topk
sparsify-and-mask) or delegates to the untouched server helpers
(federated/server._sketched/_true_topk/_local_topk/_fedavg/
_uncompressed). Dispatch moved from ``cfg.mode == ...`` branches to
the registry, but dispatch is static config — the traced round
programs are byte-identical, which graftaudit/graftnum's exact-match
baselines prove on every run.

The server helpers are imported lazily inside ``decode``:
federated/server imports config at module load, and config's spec
properties import this package, so a module-level import here would
cycle.
"""
from __future__ import annotations

from commefficient_tpu.ops.flat import clip_table_to_l2, masked_topk
from commefficient_tpu.ops.sketch import CSVec


from commefficient_tpu.compress.base import Compressor


def _fserver():
    from commefficient_tpu.federated import server as fserver
    return fserver


class SketchCompressor(Compressor):
    """FetchSGD count-sketch transport (the reference's headline
    mode): per-client [r, c] tables, linear aggregation, server-side
    top-k decode with virtual momentum/error in table space."""
    name = "sketch"
    sketch_like = True

    def wire_floats(self, cfg) -> int:
        return cfg.num_rows * cfg.num_cols

    def wire_bytes(self, cfg) -> int:
        # quantized wire transport (--sketch_table_dtype): bill at the
        # realized element size, plus int8's per-row f32 scales
        from commefficient_tpu.ops.quant import wire_table_bytes
        return wire_table_bytes(cfg.num_rows, cfg.num_cols,
                                cfg.sketch_table_dtype)

    def encode(self, cfg, grad, key=None):
        if cfg.defer_sketch_encode:
            # linearity: the round engine encodes the per-shard client
            # SUM once, instead of one table per client (Config
            # property docstring; round.py shard_train)
            return grad
        sketch = CSVec(d=cfg.grad_size, c=cfg.num_cols,
                       r=cfg.num_rows, num_blocks=cfg.num_blocks,
                       seed=42)
        table = sketch.encode(grad)
        if cfg.max_grad_norm is not None:
            table = clip_table_to_l2(
                table, sketch.l2estimate(table), cfg.max_grad_norm)
        return table

    def decode(self, cfg, gradient, Vvelocity, Verror, lr, key=None):
        return _fserver()._sketched(gradient, Vvelocity, Verror, cfg,
                                    lr, key)


class TrueTopkCompressor(Compressor):
    """Exact top-k of the summed dense gradient, selected at the
    server with virtual momentum/error feedback."""
    name = "true_topk"

    def wire_floats(self, cfg) -> int:
        return cfg.grad_size

    def decode(self, cfg, gradient, Vvelocity, Verror, lr, key=None):
        return _fserver()._true_topk(gradient, Vvelocity, Verror, cfg,
                                     lr, key)


class LocalTopkCompressor(Compressor):
    """Per-client top-k sparsification with local error feedback and
    momentum factor masking."""
    name = "local_topk"

    def wire_floats(self, cfg) -> int:
        return cfg.k

    def residual(self, cfg, to_transmit, error, velocity, key=None):
        to_transmit = masked_topk(to_transmit, k=cfg.k)
        not_sent = (to_transmit == 0).astype(to_transmit.dtype)
        if cfg.error_type == "local":
            error = error * not_sent           # error feedback
        if cfg.local_momentum > 0:
            velocity = velocity * not_sent     # momentum factor masking
        return to_transmit, error, velocity

    def decode(self, cfg, gradient, Vvelocity, Verror, lr, key=None):
        return _fserver()._local_topk(gradient, Vvelocity, Verror, cfg,
                                      lr, key)


class FedavgCompressor(Compressor):
    """Uncompressed multi-step local SGD transmitting the weighted
    weight delta (the communication-frugal baseline)."""
    name = "fedavg"
    local_sgd = True

    def wire_floats(self, cfg) -> int:
        return cfg.grad_size

    def decode(self, cfg, gradient, Vvelocity, Verror, lr, key=None):
        return _fserver()._fedavg(gradient, Vvelocity, Verror, cfg,
                                  lr, key)


class UncompressedCompressor(Compressor):
    """Dense single-step SGD — the no-compression upper bound."""
    name = "uncompressed"

    def wire_floats(self, cfg) -> int:
        return cfg.grad_size

    def decode(self, cfg, gradient, Vvelocity, Verror, lr, key=None):
        return _fserver()._uncompressed(gradient, Vvelocity, Verror,
                                        cfg, lr, key)

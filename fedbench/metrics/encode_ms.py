"""Device time per round of the layer scopes `residual` (local
momentum, local error, per-client top-k and masking) and `encode`
(sketching a client or the shard's client sum)."""
from fedbench.metrics._scopes import scope_ms


def read(ctx):
    return scope_ms(ctx, "residual", "encode")

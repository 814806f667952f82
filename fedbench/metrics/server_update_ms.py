"""Device time per round of the layer scopes `server_state` (momentum
and error, the re-sketch, zeroing what was sent, the weight update,
the cohort-row merge) and `pack_change_bits`."""
from fedbench.metrics._scopes import scope_ms


def read(ctx):
    return scope_ms(ctx, "server_state", "pack_change_bits")

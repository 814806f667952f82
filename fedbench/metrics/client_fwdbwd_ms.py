"""Device time per round of the layer scope `fwdbwd`: forward,
backward, microbatch scan, weight decay, gradient masking."""
from fedbench.metrics._scopes import scope_ms


def read(ctx):
    return scope_ms(ctx, "fwdbwd")

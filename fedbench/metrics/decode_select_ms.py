"""Device time per round of the layer scope `select`: estimates from
the sketch table and top-k or sampled threshold."""
from fedbench.metrics._scopes import scope_ms


def read(ctx):
    return scope_ms(ctx, "select")

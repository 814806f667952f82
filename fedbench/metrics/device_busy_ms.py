"""Device time per round: the union of the device-op intervals of the
traced window, averaged over the chips used."""
from fedbench.metrics._common import per_round_ms


def read(ctx):
    return per_round_ms(ctx["trace"]["busy_s"], ctx)

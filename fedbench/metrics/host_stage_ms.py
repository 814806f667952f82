"""Mean host time per round pulling the next batch from FedLoader
(sampling, fetch, augmentation, assembly): the benchmark's own span
around `next(stream)` in the driver loop."""
from fedbench.metrics._common import per_round_ms


def read(ctx):
    if not ctx["stage_s"]:
        return None
    return per_round_ms(sum(ctx["stage_s"]), ctx)

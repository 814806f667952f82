"""Device time per round of the cohort gather and scatter-back
programs (jit_gather_cohort, jit_scatter_back in the trace)."""
from fedbench.metrics._common import module_seconds, per_round_ms


def read(ctx):
    return per_round_ms(
        module_seconds(ctx, "gather_cohort", "scatter_back"), ctx)

"""Mean host time per batch in FedLoader's per-client fetch and
transform loop: the program's `load_fetch` span, from the part of the
window in which the profiler is off."""
from fedbench.metrics._scopes import untraced_span_ms


def read(ctx):
    return untraced_span_ms(ctx, "load_fetch", per="load_fetch")

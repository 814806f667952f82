"""Mean host time per batch allocating FedLoader's buffers and copying
the clients' rows into them: the program's `load_assemble` span, from
the part of the window in which the profiler is off."""
from fedbench.metrics._scopes import untraced_span_ms


def read(ctx):
    return untraced_span_ms(ctx, "load_assemble", per="load_assemble")

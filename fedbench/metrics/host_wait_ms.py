"""Mean host time per round blocked on the previous round's results:
the program's `device_wait` spans (the lagged change-bit read inside
`collect`, the telemetry materialisation), from the part of the
window in which the profiler is off."""
from fedbench.metrics._scopes import untraced_span_ms


def read(ctx):
    return untraced_span_ms(ctx, "device_wait", per="round")

"""Device idle time per round that no `fed:*` program span of the
dispatching thread covers."""
from fedbench.metrics._scopes import idle_by_span


def read(ctx):
    found = idle_by_span(ctx)
    if found is None or not ctx["rounds"]:
        return None
    return found["unattributed"] / ctx["rounds"] * 1e3

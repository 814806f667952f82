"""Device time per round of the round program's ops that lie under
none of the layer scopes."""
from fedbench.metrics._scopes import unscoped_ms


def read(ctx):
    return unscoped_ms(ctx)

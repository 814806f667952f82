"""Device ms a round under the model's layer `shared_expert`
(`commefficient_tpu/scopes.py`: the expert every position goes
through beside the routed ones), forward and backward."""
from fedbench.metrics._layers import layer_ms


def read(ctx):
    return layer_ms(ctx, "shared_expert")

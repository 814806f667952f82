"""Device ms a round under the model's layer `attention_window`
(`commefficient_tpu/scopes.py`), forward and backward."""
from fedbench.metrics._layers import layer_ms


def read(ctx):
    return layer_ms(ctx, "attention_window")

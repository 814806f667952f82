"""Device ms a round under the model's layer `norm`
(`commefficient_tpu/scopes.py`: the residual stream's RMSNorms with
the residual adds they wrap, and the final norm), forward and
backward."""
from fedbench.metrics._layers import layer_ms


def read(ctx):
    return layer_ms(ctx, "norm")

"""Device ms a round under the model's layer `attn_proj`
(`commefficient_tpu/scopes.py`: the q, k, v input projections, the
output gate's projection and product, the output projection), forward
and backward."""
from fedbench.metrics._layers import layer_ms


def read(ctx):
    return layer_ms(ctx, "attn_proj")

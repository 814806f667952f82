"""Device ms a round under the model's layer `dense_mlp`
(`commefficient_tpu/scopes.py`: a dense layer's SwiGLU), forward and
backward."""
from fedbench.metrics._layers import layer_ms


def read(ctx):
    return layer_ms(ctx, "dense_mlp")

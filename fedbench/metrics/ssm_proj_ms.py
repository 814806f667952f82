"""Device ms a round under the model's layer `ssm_proj`
(`commefficient_tpu/scopes.py`: a Mamba-2 mixer's input and output
projections), forward and backward."""
from fedbench.metrics._layers import layer_ms


def read(ctx):
    return layer_ms(ctx, "ssm_proj")

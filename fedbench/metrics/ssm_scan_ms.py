"""Device ms a round under the model's layer `ssm_scan`
(`commefficient_tpu/scopes.py`: a Mamba-2 mixer's causal convolution,
dt, chunked scan, D skip and gated norm), forward and backward."""
from fedbench.metrics._layers import layer_ms


def read(ctx):
    return layer_ms(ctx, "ssm_scan")

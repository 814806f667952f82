"""Device ms a round under the model's layer `unravel`
(`commefficient_tpu/scopes.py`: the flat vector's split into the
model's leaves and their cast; in the backward pass its transpose, the
gradient's assembly into one flat vector)."""
from fedbench.metrics._layers import layer_ms


def read(ctx):
    return layer_ms(ctx, "unravel")

"""Device ms a round under the model's layer `head_loss`
(`commefficient_tpu/scopes.py`: the head product, the softmax and the
label pick in blocks, and their sums), forward, rematerialised forward
and backward."""
from fedbench.metrics._layers import layer_ms


def read(ctx):
    return layer_ms(ctx, "head_loss")

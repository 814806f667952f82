"""Plain reference of ResNet9 (cifar10-fast): weights from a seed and the
per-example loss, in straightforward jax.numpy.

Nothing of the program is imported. The parameter tree has the names the
program's Flax module gives its own (the tree is the interface through
which the benchmark hands the program its weights), and flattens in
`jax.tree_util` order, which is the order of the program's flat vector.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def _shapes(config):
    ch = config["channels"]
    cin = config["image_channels"]
    return {
        "ConvBlock_0": (3, 3, cin, ch["prep"]),
        "ConvBlock_1": (3, 3, ch["prep"], ch["layer1"]),
        "Residual_0/ConvBlock_0": (3, 3, ch["layer1"], ch["layer1"]),
        "Residual_0/ConvBlock_1": (3, 3, ch["layer1"], ch["layer1"]),
        "ConvBlock_2": (3, 3, ch["layer1"], ch["layer2"]),
        "ConvBlock_3": (3, 3, ch["layer2"], ch["layer3"]),
        "Residual_1/ConvBlock_0": (3, 3, ch["layer3"], ch["layer3"]),
        "Residual_1/ConvBlock_1": (3, 3, ch["layer3"], ch["layer3"]),
        "head": (ch["layer3"], config["num_classes"]),
    }


def init_params(config, seed: int):
    """The whole parameter tree in one jitted call on the device."""
    shapes = _shapes(config)

    @jax.jit
    def make(key):
        tree = {}
        for i, (path, shape) in enumerate(sorted(shapes.items())):
            fan_in = 1
            for s in shape[:-1]:
                fan_in *= s
            gain = 1.0 if path == "head" else 2.0
            w = jax.random.normal(jax.random.fold_in(key, i), shape,
                                  jnp.float32) * (gain / fan_in) ** 0.5
            node = tree
            parts = path.split("/")
            for p in parts[:-1]:
                node = node.setdefault(p, {})
            if parts[-1] == "head":
                node["head"] = {"kernel": w}
            else:
                node[parts[-1]] = {"Conv_0": {"kernel": w}}
        return {"params": tree}

    return make(jax.random.PRNGKey(seed % (2 ** 31)))


def _conv(x, p):
    y = jax.lax.conv_general_dilated(
        x, p["Conv_0"]["kernel"], (1, 1), ((1, 1), (1, 1)),
        dimension_numbers=("NHWC", "HWIO", "NHWC"))
    return jnp.maximum(y, 0.0)


def _pool(x):
    n, h, w, c = x.shape
    return x.reshape(n, h // 2, 2, w // 2, 2, c).max(axis=(2, 4))


def _res(x, p):
    return x + _conv(_conv(x, p["ConvBlock_0"]), p["ConvBlock_1"])


def logits(config, params, images):
    p = params["params"]
    x = _conv(images, p["ConvBlock_0"])
    x = _pool(_conv(x, p["ConvBlock_1"]))
    x = _res(x, p["Residual_0"])
    x = _pool(_conv(x, p["ConvBlock_2"]))
    x = _pool(_conv(x, p["ConvBlock_3"]))
    x = _res(x, p["Residual_1"])
    x = x.max(axis=(1, 2))
    return (x @ p["head"]["kernel"]) * config["logit_scale"]


def example_losses(config, params, data):
    """Per-example loss [n] for one client's rows `data` = (images
    [n, 32, 32, 3] f32, labels [n])."""
    images, labels = data
    z = logits(config, params, images)
    logp = z - jax.scipy.special.logsumexp(z, axis=-1, keepdims=True)
    return -jnp.take_along_axis(
        logp, labels[:, None].astype(jnp.int32), axis=1)[:, 0]


def client_loss(config, params, data, mask):
    """One client's loss as the driver defines it: the mean of the
    per-example losses over its valid rows."""
    per = example_losses(config, params, data).astype(jnp.float32)
    return (per * mask).sum() / jnp.maximum(mask.sum(), 1.0)


def cast_data(data, dtype):
    """The control's lower precision: images in `dtype`, labels as
    they are."""
    images, labels = data
    return images.astype(dtype), labels

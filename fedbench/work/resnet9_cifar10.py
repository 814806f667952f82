"""Operations that ResNet9's forward and backward passes require, from
shapes alone (no look at the program or at XLA's count).

A 3x3 convolution over an H x W map from Cin to Cout channels is
2 * H * W * 9 * Cin * Cout floating-point operations forward; backward
needs the same again for the input's gradient and for the kernel's.
The first layer needs no input gradient. Pooling, ReLU, the residual
adds and the loss are not counted (under 1% together).
"""
from __future__ import annotations


def forward_layers(config: dict) -> list:
    """(name, forward FLOPs per image) for every matmul-like layer."""
    ch = config["channels"]
    s = config["image_size"]
    cin = config["image_channels"]

    def conv(h, a, b):
        return 2 * h * h * 9 * a * b

    return [
        ("prep", conv(s, cin, ch["prep"])),
        ("layer1", conv(s, ch["prep"], ch["layer1"])),
        ("res1.a", conv(s // 2, ch["layer1"], ch["layer1"])),
        ("res1.b", conv(s // 2, ch["layer1"], ch["layer1"])),
        ("layer2", conv(s // 2, ch["layer1"], ch["layer2"])),
        ("layer3", conv(s // 4, ch["layer2"], ch["layer3"])),
        ("res3.a", conv(s // 8, ch["layer3"], ch["layer3"])),
        ("res3.b", conv(s // 8, ch["layer3"], ch["layer3"])),
        ("head", 2 * ch["layer3"] * config["num_classes"]),
    ]


def forward_flops_per_example(config: dict) -> int:
    return sum(f for _, f in forward_layers(config))


def train_flops_per_example(config: dict, traffic: dict = None) -> int:
    """Forward plus backward for one image: three times forward, less
    the first layer's input gradient, which nothing needs. Nothing
    of the traffic changes an image's shape."""
    layers = forward_layers(config)
    return 3 * sum(f for _, f in layers) - layers[0][1]

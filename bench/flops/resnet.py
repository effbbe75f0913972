"""FLOPs of the CIFAR ResNets (He et al. 2016, §4.2) from their shapes.

Counted are the multiply-adds of the convolutions and of the head, two
FLOPs each, at the output positions and kernel taps that touch the image:
the taps that fall on SAME padding multiply zeros and are left out, as
XLA's own count leaves them out.  GroupNorm, ReLU, the residual adds and
the pooling are not model FLOPs.  A training step is three forward
passes' worth: the forward, and the backward's products with respect to
activations and to weights.
"""
from __future__ import annotations

WIDTHS = (16, 32, 64)


def _valid_taps(n: int, k: int, stride: int) -> int:
    """Sum over SAME-padded output positions of the kernel taps that fall
    inside an input of length ``n``."""
    out = -(-n // stride)
    pad = max((out - 1) * stride + k - n, 0)
    lo = pad // 2
    return sum(1 for o in range(out) for j in range(k)
               if 0 <= o * stride - lo + j < n)


def conv_layers(depth: int, image: int = 32):
    """(input size, kernel, stride, cin, cout) of every convolution."""
    n = (depth - 2) // 6
    layers = [(image, 3, 1, 3, 16)]
    size, cin = image, 16
    for s, w in enumerate(WIDTHS):
        for b in range(n):
            stride = 2 if (s > 0 and b == 0) else 1
            layers.append((size, 3, stride, cin, w))
            out = -(-size // stride)
            layers.append((out, 3, 1, w, w))
            if cin != w:
                layers.append((size, 1, stride, cin, w))
            size, cin = out, w
    return layers


def forward_flops(depth: int, num_classes: int, batch: int,
                  image: int = 32) -> int:
    """Model FLOPs of one forward pass over ``batch`` images."""
    per_image = 0
    for size, k, stride, cin, cout in conv_layers(depth, image):
        taps = _valid_taps(size, k, stride) ** 2
        per_image += 2 * taps * cin * cout
    per_image += 2 * WIDTHS[-1] * num_classes
    return batch * per_image


def train_step_flops(depth: int, num_classes: int, batch: int) -> int:
    return 3 * forward_flops(depth, num_classes, batch)

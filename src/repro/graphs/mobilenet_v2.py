"""MobileNet-v2 (Sandler et al. 2018, arXiv:1801.04381; TF-slim
``mobilenet_v2``): inverted residual blocks with linear bottlenecks.

Each block expands its input with a 1×1 conv and ReLU6 (skipped where the
expansion factor is 1), filters it with a 3×3 depthwise conv and ReLU6, and
projects it back with a **linear** 1×1 conv.  Where the block keeps stride
1 and its channel count, the projection is added to the block's input.  So
the graph is no longer a chain: each residual input stays live across the
block's widest tensors, which is what an SRAM planner has to fit.
"""
from __future__ import annotations

from repro.core.graph import Graph
from .cnn_ops import CNNBuilder

# (expansion t, output channels c, repeats n, first stride s), Table 2.
BLOCKS = [(1, 16, 1, 1), (6, 24, 2, 2), (6, 32, 3, 2), (6, 64, 4, 2),
          (6, 96, 3, 1), (6, 160, 3, 2), (6, 320, 1, 1)]
STEM_CHANNELS = 32
LAST_CHANNELS = 1280


def make_divisible(v: float, divisor: int = 8) -> int:
    """TF-slim's channel rounding: nearest multiple of ``divisor``, at
    least ``divisor``, and never more than 10% below ``v``."""
    new = max(divisor, int(v + divisor / 2) // divisor * divisor)
    return new + divisor if new < 0.9 * v else new


def mobilenet_v2_graph(alpha: float = 1.0, resolution: int = 224,
                       num_classes: int = 1001) -> Graph:
    """The published network at width ``alpha``: every channel count is
    ``make_divisible(c * alpha)``, except the last 1×1 conv, which keeps
    its 1280 channels for ``alpha <= 1`` (the paper's rule)."""
    g = Graph()
    b = CNNBuilder(g)
    x = b.input("input", resolution, resolution, 3)
    x = b.conv(x, make_divisible(STEM_CHANNELS * alpha), k=3, stride=2,
               act="relu6")
    cin = b.shapes[x][2]
    for t, c, n, s in BLOCKS:
        cout = make_divisible(c * alpha)
        for i in range(n):
            stride = s if i == 0 else 1
            y = x
            if t != 1:
                y = b.conv(y, cin * t, k=1, act="relu6")
            y = b.dwconv(y, k=3, stride=stride, act="relu6")
            y = b.conv(y, cout, k=1, act="none")
            if stride == 1 and cin == cout:
                y = b.add(y, x)
            x, cin = y, cout
    x = b.conv(x, max(LAST_CHANNELS, make_divisible(LAST_CHANNELS * alpha)),
               k=1, act="relu6")
    x = b.avgpool(x)
    x = b.fc(x, num_classes)
    g.set_outputs([x])
    return g

"""Operations the ResNet family needs, counted from shapes.

``train_flops_per_item``: forward and backward of one image, a multiply-add
counted as 2, the backward as twice the forward (no recomputation counted).
The stem is counted as the published 7x7 stride-2 convolution whatever stem
the program runs: the zero taps of the space-to-depth form are not work the
model needs.
"""

from __future__ import annotations

KERNELS = []          # no hand-written kernel on this path


def forward_flops_per_item(model: dict) -> float:
    size, nf = model["image_size"], model["num_filters"]
    hw = -(-size // 2)                                   # stem, stride 2
    total = 2.0 * hw * hw * 7 * 7 * 3 * nf
    hw = -(-hw // 2)                                     # max pool, stride 2
    cin = nf
    for i, count in enumerate(model["stage_sizes"]):
        f = nf * 2 ** i
        for j in range(count):
            stride = 2 if i > 0 and j == 0 else 1
            out = -(-hw // stride)
            total += 2.0 * hw * hw * cin * f             # 1x1
            total += 2.0 * out * out * 9 * f * f         # 3x3 (v1.5 stride)
            total += 2.0 * out * out * f * 4 * f         # 1x1
            if cin != 4 * f or stride != 1:
                total += 2.0 * out * out * cin * 4 * f   # projection
            cin, hw = 4 * f, out
    return total + 2.0 * cin * model["num_classes"]      # head


def train_flops_per_item(model: dict, job: dict) -> float:
    return 3.0 * forward_flops_per_item(model)

"""Operations the algorithm needs, from shapes: what utilization is
measured against. Never taken from the compiled program, whose count
moves when the program changes (converts, recomputation).

Convention: He et al. (arXiv:1512.03385, Table 1) give ResNet-50 as
"3.8 x 10^9 FLOPs", counting one multiply-add as one operation. Counted
from the shapes of every convolution and the classifier, the network of
that table, whose units stride in their first 1x1 convolution and which
is what the program's symbol builds, needs 3.858e9 multiply-adds an
image at 224x224. The often quoted 4.089e9 ("4.1 GFLOPs") belongs to the
variant that strides in the 3x3 convolution ("v1.5"); it is not this
network, and a utilization worked out from it would read 6% too high.
A multiply-add is 2 floating-point operations, and a training step is
forward plus backward = 3 forward passes (one for the outputs, one each
for the gradients of inputs and of weights). BatchNorm, ReLU, pooling and
the optimizer are bandwidth, not FLOPs, and are left out; recomputed
work is not counted.
"""
from __future__ import annotations

import importlib


def _reference(cfg):
    return importlib.import_module("references." + cfg["reference"])


def forward_macs_per_image(cfg):
    convs, c_last = _reference(cfg).conv_plan(
        cfg["num_layers"], cfg["image_shape"][1])
    macs = sum(c_in * c_out * k * k * hw * hw
               for _name, c_in, c_out, k, _stride, hw in convs)
    return macs + c_last * cfg["num_classes"]


def train_flops_per_image(cfg):
    return 3 * 2 * forward_macs_per_image(cfg)

"""Operations a configuration requires, computed from its shapes.

Model FLOP/s utilisation divides the operations the forward and
backward passes *require* by the chip's peak, so the count is a
function of the configuration's file and of nothing the program does:
recomputed, padded or fused operations neither add to it nor take from
it.  Only convolutions and dense layers are counted (multiply-
accumulates on the MXU); normalisation, activations, pooling and the
loss are elementwise or reductions and are left out, as is customary.

A layer is ``(name, macs, needs_input_gradient)``.  Training costs the
forward pass, the gradient with respect to the weights, and the
gradient with respect to the input, each as many multiply-accumulates
as the forward pass — except that the layer that reads the images
needs no input gradient.
"""

from __future__ import annotations

from typing import List, Tuple

Layer = Tuple[str, int, bool]

# 3x3 convolutions per stage, by depth: Simonyan & Zisserman 2014,
# table 1 (A, B, D, E)
_VGG_STAGES = {11: (1, 1, 2, 2, 2), 13: (2, 2, 2, 2, 2),
               16: (2, 2, 3, 3, 3), 19: (2, 2, 4, 4, 4)}


def same_out(size: int, stride: int) -> int:
    """Output length of a SAME-padded convolution or pooling."""
    return -(-size // stride)


def conv2d_macs(out_h: int, out_w: int, k_h: int, k_w: int,
                c_in: int, c_out: int) -> int:
    return out_h * out_w * k_h * k_w * c_in * c_out


def dense_macs(n_in: int, n_out: int) -> int:
    return n_in * n_out


def resnet_v1_5_layers(config: dict) -> List[Layer]:
    """ResNet v1.5 with bottleneck blocks (He et al. 2015; the stride
    sits on the 3x3 convolution as in torchvision): 7x7/2 stem, 3x3/2
    max-pool, ``stage_sizes`` blocks of 1x1 -> 3x3 -> 1x1(x4) with a 1x1
    projection where the shape changes, global mean, one dense layer."""
    if config["block"] != "bottleneck" or config["stem"] != "conv7":
        raise ValueError(
            "resnet_v1_5_layers counts bottleneck blocks behind a 7x7 "
            f"stem; got block={config['block']!r} stem={config['stem']!r}")
    f0, chans = config["num_filters"], config["image_channels"]
    size = same_out(config["image_size"], 2)
    layers: List[Layer] = [
        ("conv_init", conv2d_macs(size, size, 7, 7, chans, f0), False)]
    size = same_out(size, 2)  # max-pool
    c_in = f0
    for i, blocks in enumerate(config["stage_sizes"]):
        f = f0 * 2 ** i
        for j in range(blocks):
            stride = 2 if i > 0 and j == 0 else 1
            out = same_out(size, stride)
            name = f"stage{i}.block{j}"
            layers += [
                (f"{name}.conv1x1a", conv2d_macs(size, size, 1, 1, c_in, f),
                 True),
                (f"{name}.conv3x3", conv2d_macs(out, out, 3, 3, f, f), True),
                (f"{name}.conv1x1b", conv2d_macs(out, out, 1, 1, f, 4 * f),
                 True)]
            if stride != 1 or c_in != 4 * f:
                layers.append(
                    (f"{name}.conv_proj",
                     conv2d_macs(out, out, 1, 1, c_in, 4 * f), True))
            size, c_in = out, 4 * f
    layers.append(("dense", dense_macs(c_in, config["num_classes"]), True))
    return layers


def vgg_layers(config: dict) -> List[Layer]:
    """VGG (Simonyan & Zisserman 2014): stages of SAME 3x3 convolutions
    at ``conv_widths`` channels (64-128-256-512-512), each followed by a
    2x2/2 max-pool, then dense layers of ``dense_width`` twice and one
    to the classes."""
    size, c_in = config["image_size"], config["image_channels"]
    layers: List[Layer] = []
    for i, (reps, width) in enumerate(
            zip(_VGG_STAGES[config["depth"]], config["conv_widths"])):
        for j in range(reps):
            layers.append(
                (f"conv{i}_{j}",
                 conv2d_macs(size, size, 3, 3, c_in, width), bool(layers)))
            c_in = width
        size //= 2
    flat, width = size * size * c_in, config["dense_width"]
    layers += [("dense0", dense_macs(flat, width), True),
               ("dense1", dense_macs(width, width), True),
               ("dense2", dense_macs(width, config["num_classes"]), True)]
    return layers


LAYERS_BY_ARCHITECTURE = {
    "resnet_v1_5": resnet_v1_5_layers,
    "vgg": vgg_layers,
}


def forward_macs(layers: List[Layer]) -> int:
    return sum(macs for _, macs, _ in layers)


def train_flops(layers: List[Layer]) -> int:
    """FLOPs (2 per multiply-accumulate) one sample requires of a
    training step: forward, weight gradient, and input gradient where
    the layer's input needs one."""
    return 2 * sum(macs * (3 if needs_dx else 2)
                   for _, macs, needs_dx in layers)

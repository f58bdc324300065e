"""The analytic operation counts against counts made by hand."""

import json
import os

import pytest

from benchmark import cells, flops


def _config(name):
    with open(os.path.join(cells.HERE, "configs", name + ".json")) as f:
        return json.load(f)


def test_single_layers_by_hand():
    # ResNet-50's stem: 7x7 over 3 channels into 64, on a 112x112 output
    assert flops.conv2d_macs(112, 112, 7, 7, 3, 64) == 118_013_952
    # VGG-16's first convolution: 3x3 over 3 channels into 64, at 224x224
    assert flops.conv2d_macs(224, 224, 3, 3, 3, 64) == 86_704_128
    # VGG-16's first dense layer: 7*7*512 = 25088 inputs into 4096
    assert flops.dense_macs(25088, 4096) == 102_760_448
    assert flops.same_out(224, 2) == 112 and flops.same_out(7, 2) == 4


def test_resnet50_is_4_1_gmac_forward():
    layers = flops.resnet_v1_5_layers(_config("resnet50"))
    by_name = {name: macs for name, macs, _ in layers}
    assert len(layers) == 54          # 53 convolutions and the classifier
    assert by_name["conv_init"] == 118_013_952
    # first block: 1x1 64->64, 3x3 64->64, 1x1 64->256 and the 1x1
    # projection 64->256, all at 56x56
    assert by_name["stage0.block0.conv1x1a"] == 56 * 56 * 64 * 64
    assert by_name["stage0.block0.conv3x3"] == 56 * 56 * 9 * 64 * 64
    assert by_name["stage0.block0.conv_proj"] == 56 * 56 * 64 * 256
    # v1.5: the stride sits on the 3x3, so the 1x1 before it still runs
    # at the input's 56x56 and the 3x3 at 28x28
    assert by_name["stage1.block0.conv1x1a"] == 56 * 56 * 256 * 128
    assert by_name["stage1.block0.conv3x3"] == 28 * 28 * 9 * 128 * 128
    assert by_name["dense"] == 2048 * 1000
    assert flops.forward_macs(layers) == 4_089_184_256
    assert flops.forward_macs(layers) == pytest.approx(4.1e9, rel=0.01)


def test_vgg16_is_15_5_gmac_forward():
    layers = flops.vgg_layers(_config("vgg16"))
    assert [name for name, _, _ in layers][:3] == [
        "conv0_0", "conv0_1", "conv1_0"]
    assert len(layers) == 16
    assert flops.forward_macs(layers) == 15_470_264_320
    assert flops.forward_macs(layers) == pytest.approx(15.5e9, rel=0.01)


def test_training_is_three_passes_less_the_image_gradient():
    layers = [("first", 100, False), ("second", 10, True)]
    # forward + weight gradient for both, input gradient for the second
    assert flops.train_flops(layers) == 2 * (100 * 2 + 10 * 3)


@pytest.mark.parametrize("name", ["resnet50", "vgg16"])
def test_a_configuration_states_what_the_function_gives(name):
    config = _config(name)
    layers = flops.LAYERS_BY_ARCHITECTURE[config["architecture"]](config)
    assert config["forward_macs_per_sample"] == flops.forward_macs(layers)
    assert config["train_flops_per_sample"] == flops.train_flops(layers)

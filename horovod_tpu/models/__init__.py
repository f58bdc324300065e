from .mlp import MLP
from .transformer import (
    TransformerConfig,
    init_params as transformer_init_params,
    make_loss_fn as transformer_loss_fn,
    make_train_step as transformer_train_step,
    param_specs as transformer_param_specs,
)
from .resnet import (
    ResNet,
    ResNet18,
    ResNet34,
    ResNet50,
    ResNet101,
    ResNet152,
)
from .vgg import VGG, VGG16, VGG19
from .inception import InceptionV3

__all__ = [
    "MLP",
    "TransformerConfig",
    "transformer_init_params",
    "transformer_loss_fn",
    "transformer_train_step",
    "transformer_param_specs",
    "ResNet",
    "ResNet18",
    "ResNet34",
    "ResNet50",
    "ResNet101",
    "ResNet152",
    "VGG", "VGG16", "VGG19",
    "InceptionV3",
    # a submodule, imported when asked for (``from horovod_tpu.models
    # import looped``): the other jobs' set-up is imports first
    "looped",
    "hybrid_moe",
    "kimi_linear",
    "gated_conv",
]

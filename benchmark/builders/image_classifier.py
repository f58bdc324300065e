"""Image classifiers of ``horovod_tpu.models`` under softmax cross-
entropy: what a configuration's file has to say to get one built.

``build(config)`` returns the pieces the shared train job needs — how
to initialise, the loss, a pool of synthetic samples — and what the
yardstick needs: the operations a sample requires and the loss to
expect from random weights.  Nothing here knows a cell or a traffic
mix.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Callable, Dict

from benchmark import flops


@dataclasses.dataclass(frozen=True)
class Workload:
    init: Callable            # (key, example rows) -> (params, model_state)
    loss_fn: Callable         # (params, model_state, batch) -> (loss, state)
    make_pool: Callable       # (numpy Generator, rows, dtype name) ->
                              # {"x", "y"} on the host
    sample_unit: str
    samples_per_row: int      # an image is one row; a sequence is many tokens
    train_flops_per_sample: int
    expected_first_loss: float


def _model(config: Dict[str, Any]):
    import jax.numpy as jnp

    from horovod_tpu import models

    dtype = jnp.dtype(config["compute_dtype"])
    arch = config["architecture"]
    if arch == "resnet_v1_5":
        # flops.resnet_v1_5_layers refuses any block but "bottleneck"
        return models.ResNet(
            stage_sizes=list(config["stage_sizes"]),
            block_cls=models.resnet.BottleneckBlock,
            num_classes=config["num_classes"],
            num_filters=config["num_filters"], dtype=dtype,
            bn_axis_name=config["batch_norm"]["sync_axis"],
            stem=config["stem"], remat=config["remat"])
    if arch == "vgg":
        # models.VGG takes no widths: refuse a file that states others
        # than it builds, so that flops.py counts the model that runs
        built = {"conv_widths": list(models.vgg._WIDTHS),
                 "dense_width": 4096}
        stated = {key: config[key] for key in built}
        if stated != built:
            raise ValueError(
                f"models.VGG builds {built}; {config['name']} states "
                f"{stated}")
        return models.VGG(depth=config["depth"],
                          num_classes=config["num_classes"], dtype=dtype)
    raise ValueError(
        f"image_classifier builds {sorted(flops.LAYERS_BY_ARCHITECTURE)}, "
        f"not architecture {arch!r}")


def build(config: Dict[str, Any]) -> Workload:
    import jax.numpy as jnp
    import numpy as np
    import optax

    model = _model(config)
    px, chans = config["image_size"], config["image_channels"]
    classes = config["num_classes"]
    layers = flops.LAYERS_BY_ARCHITECTURE[config["architecture"]](config)

    def init(key, rows):
        variables = model.init(key, rows, train=True)
        return variables["params"], variables.get("batch_stats", {})

    def loss_fn(params, model_state, batch):
        logits, mutated = model.apply(
            {"params": params, "batch_stats": model_state}, batch["x"],
            train=True, mutable=["batch_stats"])
        loss = optax.softmax_cross_entropy_with_integer_labels(
            logits, batch["y"]).mean()
        return loss, mutated.get("batch_stats", {})

    def make_pool(rng, rows, dtype):
        # made by numpy in ordinary host memory, in the type the traffic
        # mix feeds.  (Drawn on the device and read back with
        # np.asarray, the same pool made every ArraySource.fetch three
        # times slower on the chip's host — PERF.md, findings of PR 22.)
        images = rng.standard_normal(
            (rows, px, px, chans), dtype=np.float32).astype(jnp.dtype(dtype))
        labels = rng.integers(0, classes, (rows,), dtype=np.int32)
        return {"x": images, "y": labels}

    return Workload(
        init=init, loss_fn=loss_fn, make_pool=make_pool,
        sample_unit=config["sample_unit"], samples_per_row=1,
        train_flops_per_sample=flops.train_flops(layers),
        # random weights spread the logits evenly over the classes
        expected_first_loss=math.log(classes))

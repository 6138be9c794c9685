"""The ``resnet`` family: the paper's encoder on images (ResNet with
weight standardization and GroupNorm, then the MLP head). What the
harness asks of a family, found by the configuration's ``"family"``:

    model_config(model) -> (ModelConfig, DualEncoderConfig) of the program
    make_inputs(traffic, model, seed) -> (leaves, shapes, labels): each
        per-sample leaf as (N, elements) host rows by its name in the
        program's batch, its sample shape, and the labels
    flops_per_sample(model, local_steps) -> model FLOPs of one client
        sample, the numerator of ``mfu`` and ``step_mfu``
"""
from __future__ import annotations

from bench import flops, traffic


def model_config(model: dict):
    from repro.configs.base import DualEncoderConfig, ModelConfig
    cfg = ModelConfig(
        name=model["name"], family="resnet", source=model["source"],
        num_layers=model["num_layers"], d_model=model["resnet_channels"][-1],
        num_heads=1, num_kv_heads=1, d_ff=0, vocab_size=0,
        resnet_stages=tuple(model["resnet_stages"]),
        resnet_channels=tuple(model["resnet_channels"]),
        resnet_groups=model["resnet_groups"],
        resnet_in_channels=model["resnet_in_channels"],
        image_size=model["image_size"], dtype=model["dtype"])
    de_cfg = DualEncoderConfig(proj_dims=tuple(model["proj_dims"]),
                               lambda_cco=model["lambda_cco"],
                               shared_towers=True)
    return cfg, de_cfg


def make_inputs(traffic_spec: dict, model: dict, seed: int):
    s = model["image_size"]
    shape = (s, s, model["resnet_in_channels"])
    images, labels = traffic.make_dataset(traffic_spec, seed, shape=shape)
    return {"images": images}, {"images": shape}, labels


def flops_per_sample(model: dict, local_steps: int) -> float:
    return flops.model_flops_per_sample(model, local_steps)

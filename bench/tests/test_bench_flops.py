"""bench/flops.py against XLA's own count of one jitted encoder-and-head
forward at batch 1.

The two shape counts bracket XLA's: ``valid_tap_flops`` leaves out the
taps on SAME padding and all elementwise work (weight standardization,
GroupNorm, ReLU), which XLA counts; ``forward_flops`` (the mfu convention)
counts every tap. Measured on the CPU: 0.775 <= XLA <= 0.847 GFLOP for
ResNet-14 and 0.969 <= 1.042 <= 1.116 for ResNet-18. A block or a stride
counted wrong moves a count out of that bracket, or the bracket past 10%.
"""
import jax
import jax.numpy as jnp
import pytest

from bench import flops, reference, run


@pytest.mark.parametrize("config", ["resnet14-cifar", "resnet18-cifar"])
def test_flops_match_xla_cost_analysis(config):
    spec = run.load_cell({"resnet14-cifar": "r14-k256-n2",
                          "resnet18-cifar": "r18-k256-q1to6-stream"}[config])
    model = spec["model"]
    params = jax.eval_shape(lambda k: reference.init_params(k, model),
                            jax.random.PRNGKey(0))
    s = model["image_size"]
    x = jax.ShapeDtypeStruct((1, s, s, model["resnet_in_channels"]),
                             jnp.float32)
    cost = jax.jit(lambda p, x: reference.encode(model, p, x)).lower(
        params, x).compile().cost_analysis()
    if isinstance(cost, list):
        cost = cost[0]
    xla = cost["flops"]
    valid = flops.valid_tap_flops(model)
    full = flops.forward_flops(model)
    assert valid < xla < full, (valid, xla, full)
    assert full < 1.20 * valid
    assert flops.model_flops_per_sample(model, 1) == 6 * full

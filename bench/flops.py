"""Operations of the encoder and head, counted from the configuration's
shapes.

``forward_flops`` is the model-FLOP convention behind ``mfu``: every
multiply-add of every convolution tap (2 FLOPs), padding taps included,
plus the head's matrix products. ``valid_tap_flops`` leaves out the taps
that land on SAME padding, which is what XLA's ``cost_analysis`` counts
for a convolution; the tests compare it with XLA's count.
"""
from __future__ import annotations


def _conv_layers(model):
    """(out_hw, k, cin, cout, stride, in_hw) of every convolution."""
    hw = model["image_size"]
    ch = model["resnet_channels"]
    layers = [(hw, 3, model["resnet_in_channels"], ch[0], 1, hw)]
    cin = ch[0]
    for si, (n_blocks, c) in enumerate(zip(model["resnet_stages"], ch)):
        for bi in range(n_blocks):
            stride = 2 if (bi == 0 and si > 0) else 1
            out = -(-hw // stride)
            layers.append((out, 3, cin, c, stride, hw))
            layers.append((out, 3, c, c, 1, out))
            if stride != 1 or cin != c:
                layers.append((out, 1, cin, c, stride, hw))
            hw, cin = out, c
    return layers


def head_flops(model) -> float:
    dims = [model["resnet_channels"][-1]] + list(model["proj_dims"])
    return float(sum(2 * a * b for a, b in zip(dims, dims[1:])))


def forward_flops(model) -> float:
    """Forward FLOPs of one image through encoder and head."""
    conv = sum(2.0 * hw * hw * k * k * cin * cout
               for hw, k, cin, cout, _, _ in _conv_layers(model))
    return conv + head_flops(model)


def _valid_taps(out: int, k: int, stride: int, size_in: int) -> int:
    """Taps of a k-wide SAME window, summed over ``out`` output positions
    along one axis, that land inside the input."""
    pad_total = max((out - 1) * stride + k - size_in, 0)
    lo = pad_total // 2
    return sum(1 for o in range(out) for t in range(k)
               if 0 <= o * stride + t - lo < size_in)


def valid_tap_flops(model) -> float:
    """``forward_flops`` without the taps on SAME padding."""
    total = 0.0
    for hw, k, cin, cout, stride, size_in in _conv_layers(model):
        taps = _valid_taps(hw, k, stride, size_in)
        total += 2.0 * taps * taps * cin * cout
    return total + head_flops(model)


def model_flops_per_sample(model, local_steps: int) -> float:
    """Training FLOPs per client sample (a pair of views): forward and
    backward (3x forward) of both views, per local step. The phase-1
    forward repeats the first local step's forward on the same
    parameters, a recompute, and is not counted."""
    return 2 * 3 * forward_flops(model) * local_steps

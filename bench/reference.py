"""Plain float32 reference of the paper's encoder (arXiv:2210.00092 Sec
4.2-4.3): a ResNet with weight standardization and GroupNorm, then an MLP
head, its seeded initialisation and its two views of an image; written
from the paper and independent of ``src/``. The round around it (cohort,
statistics, loss, Adam) is ``bench/protocol.py``'s; this module is the
``"reference"`` of the ``resnet`` configurations.

Every product runs at ``Precision.HIGHEST`` (float32 on a TPU), each
operand through ``protocol.lowp`` so that the control can lower it.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bench.protocol import lowp

F32 = jnp.float32
HI = jax.lax.Precision.HIGHEST


# -- parameters -----------------------------------------------------------------

def block_plan(model):
    """(cin, cout, stride) of each residual block."""
    plan, cin = [], model["resnet_channels"][0]
    for si, (n_blocks, c) in enumerate(zip(model["resnet_stages"],
                                           model["resnet_channels"])):
        for bi in range(n_blocks):
            plan.append((cin, c, 2 if (bi == 0 and si > 0) else 1))
            cin = c
    return plan


def _conv_w(key, kh, kw, cin, cout):
    w = jax.random.truncated_normal(key, -2, 2, (kh, kw, cin, cout), F32)
    return {"w": w / np.sqrt(kh * kw * cin)}


def _gn(c):
    return {"scale": jnp.ones((c,), F32), "bias": jnp.zeros((c,), F32)}


def init_params(key, model):
    """Seeded float32 parameters in the program's pytree layout
    (``{"tower": ..., "proj": {"layers": [...]}}``); jit it to make them
    on the device in one call."""
    k_tower, k_proj = jax.random.split(key)
    keys = iter(jax.random.split(k_tower, 256))
    ch = model["resnet_channels"]
    tower = {"stem": _conv_w(next(keys), 3, 3, model["resnet_in_channels"],
                             ch[0]),
             "stem_gn": _gn(ch[0]), "blocks": []}
    for cin, c, stride in block_plan(model):
        blk = {"conv1": _conv_w(next(keys), 3, 3, cin, c), "gn1": _gn(c),
               "conv2": _conv_w(next(keys), 3, 3, c, c), "gn2": _gn(c)}
        if stride != 1 or cin != c:
            blk["proj"] = _conv_w(next(keys), 1, 1, cin, c)
        tower["blocks"].append(blk)
    dims = (ch[-1],) + tuple(model["proj_dims"])
    layers = []
    for i, k in enumerate(jax.random.split(k_proj, len(dims) - 1)):
        w = jax.random.truncated_normal(k, -2, 2, (dims[i], dims[i + 1]), F32)
        layers.append({"w": w / np.sqrt(dims[i]),
                       "b": jnp.zeros((dims[i + 1],), F32)})
    return {"tower": tower, "proj": {"layers": layers}}


# -- encoder --------------------------------------------------------------------

def _conv(p, x, stride=1, dt=None):
    w = p["w"]
    mu = w.mean(axis=(0, 1, 2), keepdims=True)
    var = ((w - mu) ** 2).mean(axis=(0, 1, 2), keepdims=True)
    w = (w - mu) / jnp.sqrt(var + 1e-5)          # weight standardization
    return jax.lax.conv_general_dilated(
        lowp(x, dt), lowp(w, dt), (stride, stride), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=HI)


def _groupnorm(x, groups, p, eps=1e-5):
    """Each group of C/G channels normalized over them and all pixels
    (Wu & He 2018), from per-channel means, so that no array has a minor
    dimension of C/G."""
    b, _, _, c = x.shape

    def group_mean(t):                       # (B, H, W, C) -> (B, 1, 1, C)
        m = t.mean(axis=(1, 2)).reshape(b, groups, c // groups).mean(-1)
        return jnp.repeat(m, c // groups, axis=-1).reshape(b, 1, 1, c)

    xc = x - group_mean(x)
    xn = xc / jnp.sqrt(group_mean(xc * xc) + eps)
    return xn * p["scale"] + p["bias"]


def encode(model, params, images, dt=None):
    """images (B, H, W, C) -> projections (B, proj_dims[-1]); ``dt`` is
    the dtype of the products' operands (None: float32)."""
    g = model["resnet_groups"]
    t = params["tower"]
    x = _conv(t["stem"], images, 1, dt)
    x = jax.nn.relu(_groupnorm(x, min(g, x.shape[-1]), t["stem_gn"]))
    for blk, (cin, c, stride) in zip(t["blocks"], block_plan(model)):
        h = _conv(blk["conv1"], x, stride, dt)
        h = jax.nn.relu(_groupnorm(h, min(g, c), blk["gn1"]))
        h = _groupnorm(_conv(blk["conv2"], h, 1, dt), min(g, c), blk["gn2"])
        sc = _conv(blk["proj"], x, stride, dt) if "proj" in blk else x
        x = jax.nn.relu(h + sc)
    z = x.mean(axis=(1, 2))
    layers = params["proj"]["layers"]
    for i, lp in enumerate(layers):
        z = jnp.dot(lowp(z, dt), lowp(lp["w"], dt), precision=HI) + lp["b"]
        if i < len(layers) - 1:
            z = jax.nn.relu(z)
    return z


# -- views --------------------------------------------------------------------------

def augment(key, img, crop_frac=0.8):
    """Random crop and nearest resize, horizontal flip, brightness and
    contrast jitter, clipped to [0, 1] (the paper's App. B views minus
    blur)."""
    kc, kf, kb, kcon = jax.random.split(key, 4)
    h, w, c = img.shape
    ch, cw = int(h * crop_frac), int(w * crop_frac)
    top = jax.random.randint(kc, (), 0, h - ch + 1)
    left = jax.random.randint(kc, (), 0, w - cw + 1)
    crop = jax.lax.dynamic_slice(img, (top, left, 0), (ch, cw, c))
    rows = (jnp.arange(h) * ch // h).astype(jnp.int32)
    cols = (jnp.arange(w) * cw // w).astype(jnp.int32)
    out = crop[rows][:, cols]
    out = jnp.where(jax.random.bernoulli(kf), out[:, ::-1], out)
    brightness = 1.0 + 0.4 * (jax.random.uniform(kb) - 0.5)
    contrast = 1.0 + 0.4 * (jax.random.uniform(kcon) - 0.5)
    mean = out.mean()
    return jnp.clip((out - mean) * contrast + mean * brightness, 0.0, 1.0)


def two_views(key, img):
    k1, k2 = jax.random.split(key)
    return augment(k1, img), augment(k2, img)


def views(key, sample):
    """The two views of one sample (``{"images": (H, W, C)}``)."""
    return two_views(key, sample["images"])

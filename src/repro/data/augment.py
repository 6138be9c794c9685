"""Two-view augmentations (paper App. B: BYOL augmentations minus blur for
images; token analogues for sequence modalities).

All augmentations are stateless jax functions keyed by an explicit PRNGKey —
the paper's footnote 3 attributes its centralized/federated gap to stateful
vs stateless RNG; we are stateless everywhere by construction.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


# ------------------------------------------------------------------ images --

def crop_resize_flip(img, top, left, flip, crop_frac: float = 0.8):
    """The (ch, cw) crop at (top, left), nearest-resized back to (h, w)
    and mirrored left-right where ``flip``. img: (H,W,C).

    Offsets, resize map and flip fold into one source row and one source
    column a pixel, selected by one-hot products ``R @ X @ P`` on the
    image's (H, W*C) rows, with no gather and no dynamic slice.
    """
    h, w, c = img.shape
    ch, cw = int(h * crop_frac), int(w * crop_frac)
    rows = top + (jnp.arange(h) * ch // h)
    j = jnp.arange(w)
    cols = left + (jnp.where(flip, w - 1 - j, j) * cw // w)
    sel_rows = (rows[:, None] == jnp.arange(h)[None, :]).astype(img.dtype)
    # column one-hot expanded over channels: (W*C, w*c)
    sel_cols = ((cols[None, :] == j[:, None])[:, None, :, None]
                & jnp.eye(c, dtype=bool)[None, :, None, :])
    sel_cols = sel_cols.reshape(w * c, w * c).astype(img.dtype)
    hi = jax.lax.Precision.HIGHEST
    out = jnp.dot(jnp.dot(sel_rows, img.reshape(h, w * c), precision=hi),
                  sel_cols, precision=hi)
    return out.reshape(h, w, c)


def augment_image(key, img, crop_frac: float = 0.8):
    """Random crop-and-resize (nearest), flip, color jitter. img: (H,W,C).

    The crop, resize and flip are ``crop_resize_flip``'s one-hot products:
    under ``vmap`` a ``dynamic_slice`` becomes a gather that XLA on the TPU
    runs as a serial loop of one ``dynamic-update-slice`` a sample. They
    run at HIGHEST, where a one-hot product is exact in f32; the TPU's
    default precision (one bf16 pass) would round every pixel to bf16.
    """
    kc, kf, kb, kcon = jax.random.split(key, 4)
    h, w, c = img.shape
    ch, cw = int(h * crop_frac), int(w * crop_frac)
    top = jax.random.randint(kc, (), 0, h - ch + 1)
    left = jax.random.randint(kc, (), 0, w - cw + 1)
    out = crop_resize_flip(img, top, left, jax.random.bernoulli(kf),
                           crop_frac)
    brightness = 1.0 + 0.4 * (jax.random.uniform(kb) - 0.5)
    contrast = 1.0 + 0.4 * (jax.random.uniform(kcon) - 0.5)
    mean = out.mean()
    return jnp.clip((out - mean) * contrast + mean * brightness, 0.0, 1.0)


def two_views_image(key, img):
    k1, k2 = jax.random.split(key)
    return augment_image(k1, img), augment_image(k2, img)


# ------------------------------------------------------------------ tokens --

def augment_tokens(key, tokens, vocab: int, mask_token: int = 0,
                   mask_prob: float = 0.15, crop_prob: float = 0.5,
                   max_crop_frac: float = 0.25):
    """Span-mask + random-crop-with-roll: the token analogue of crop+jitter."""
    km, kc, ks, kr = jax.random.split(key, 4)
    s = tokens.shape[-1]
    masked = jnp.where(jax.random.bernoulli(km, mask_prob, tokens.shape),
                       jnp.asarray(mask_token, tokens.dtype), tokens)
    # random circular shift (crop analogue; keeps shape static)
    do_crop = jax.random.bernoulli(kc, crop_prob)
    shift = jax.random.randint(ks, (), 0, max(1, int(s * max_crop_frac)))
    rolled = jnp.roll(masked, shift, axis=-1)
    return jnp.where(do_crop, rolled, masked)


def two_views_tokens(key, tokens, vocab: int, **kw):
    k1, k2 = jax.random.split(key)
    return (augment_tokens(k1, tokens, vocab, **kw),
            augment_tokens(k2, tokens, vocab, **kw))

"""Shared neural-net primitives (pure JAX, param pytrees are nested dicts).

Conventions:
  * params are dicts of jnp arrays; every creator takes (key, ...) and returns the dict.
  * compute dtype follows the input; params are stored in cfg.dtype (bf16 by
    default) except norms/scales kept in f32.
  * all matmuls accumulate in f32 via ``preferred_element_type``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32


def dtype_of(name: str):
    return {"bfloat16": jnp.bfloat16, "float32": jnp.float32, "float16": jnp.float16}[name]


# ----------------------------------------------------------------- linear ---

def linear_init(key, d_in: int, d_out: int, dtype=jnp.bfloat16, bias: bool = False,
                scale: float = 1.0):
    std = scale / np.sqrt(d_in)
    p = {"w": (jax.random.truncated_normal(key, -2, 2, (d_in, d_out), F32) * std).astype(dtype)}
    if bias:
        p["b"] = jnp.zeros((d_out,), dtype)
    return p


# Cross-device matmul reduction dtype. XLA places the tensor-parallel
# all-reduce on the dot output BEFORE the cast back to the activation dtype,
# so with preferred_element_type=f32 the partial sums cross the ICI in f32 —
# 2x the necessary wire bytes. Setting bf16 here halves TP collective
# payloads at a small cross-device accumulation-precision cost (a standard
# production knob; see EXPERIMENTS §Perf). None = f32 (default, exact).
_MATMUL_PREFERRED = {"dtype": None}


def set_matmul_preferred(dtype) -> None:
    _MATMUL_PREFERRED["dtype"] = dtype


def linear(p, x):
    pe = _MATMUL_PREFERRED["dtype"] or F32
    y = jnp.einsum("...i,io->...o", x, p["w"], preferred_element_type=pe)
    if "b" in p:
        y = y + p["b"].astype(pe)
    return y.astype(x.dtype)


# ------------------------------------------------------------------ norms ---

def rmsnorm_init(d: int):
    return {"scale": jnp.ones((d,), F32)}


def rmsnorm(p, x, eps: float = 1e-6):
    xf = x.astype(F32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps) * p["scale"]
    return y.astype(x.dtype)


def layernorm_init(d: int):
    return {"scale": jnp.ones((d,), F32), "bias": jnp.zeros((d,), F32)}


def layernorm(p, x, eps: float = 1e-6):
    xf = x.astype(F32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]
    return y.astype(x.dtype)


def groupnorm(x, num_groups: int, scale, bias, eps: float = 1e-5):
    """GroupNorm (Wu & He; paper Sec 4.2) over channel-last input
    (B, ..., C): per sample, each group of C/G channels is normalized over
    those channels and all positions between the batch and channel axes.
    The moments are pooled from per-channel (B, C) sums, so no array
    carries a minor dim of C/G (2..8 here, padded to 128 lanes on a TPU)."""
    b, c = x.shape[0], x.shape[-1]
    assert c % num_groups == 0
    xf = x.astype(F32)
    bshape = (b,) + (1,) * (x.ndim - 2) + (c,)

    def group_mean(t):                            # (B, ..., C) -> bshape
        m = jnp.mean(t, axis=tuple(range(1, t.ndim - 1)))          # (B, C)
        m = jnp.mean(m.reshape(b, num_groups, c // num_groups), axis=-1)
        return jnp.repeat(m, c // num_groups, axis=-1).reshape(bshape)

    mu = group_mean(xf)
    xc = xf - mu
    xf = xc * jax.lax.rsqrt(group_mean(jnp.square(xc)) + eps)
    return (xf * scale + bias).astype(x.dtype)


# ------------------------------------------------------------------- rope ---

def rope_frequencies(head_dim: int, theta: float):
    return 1.0 / (theta ** (np.arange(0, head_dim, 2, dtype=np.float32) / head_dim))


def apply_rope(x, positions, theta: float = 10000.0):
    """x: (..., S, H, Dh) rotated pairwise; positions: (..., S) or (S,)."""
    dh = x.shape[-1]
    freqs = jnp.asarray(rope_frequencies(dh, theta))          # (dh/2,)
    angles = positions.astype(F32)[..., None] * freqs          # (..., S, dh/2)
    angles = angles[..., None, :]                              # (..., S, 1, dh/2)
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = x[..., : dh // 2], x[..., dh // 2:]
    xf1, xf2 = x1.astype(F32), x2.astype(F32)
    out = jnp.concatenate([xf1 * cos - xf2 * sin, xf2 * cos + xf1 * sin], axis=-1)
    return out.astype(x.dtype)


# -------------------------------------------------------------- embedding ---

def embedding_init(key, vocab: int, d_model: int, dtype=jnp.bfloat16):
    emb = jax.random.normal(key, (vocab, d_model), F32) * 0.02
    return {"table": emb.astype(dtype)}


def embed(p, tokens):
    return jnp.take(p["table"], tokens, axis=0)


def unembed(p, x):
    """Tied unembedding: (..., D) @ (V, D)^T -> logits (..., V)."""
    return jnp.einsum("...d,vd->...v", x, p["table"], preferred_element_type=F32)


# ------------------------------------------------------------------- misc ---

def swiglu_init(key, d_model: int, d_ff: int, dtype=jnp.bfloat16):
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "gate": linear_init(k1, d_model, d_ff, dtype),
        "up": linear_init(k2, d_model, d_ff, dtype),
        "down": linear_init(k3, d_ff, d_model, dtype),
    }


def swiglu(p, x):
    return linear(p["down"], jax.nn.silu(linear(p["gate"], x)) * linear(p["up"], x))


def mlp_init(key, dims, dtype=jnp.bfloat16, bias=True):
    """Plain MLP for projection heads: dims = (d_in, h1, ..., d_out)."""
    keys = jax.random.split(key, len(dims) - 1)
    return {"layers": [linear_init(k, dims[i], dims[i + 1], dtype, bias=bias)
                       for i, k in enumerate(keys)]}


def mlp(p, x, final_activation=False):
    n = len(p["layers"])
    for i, lp in enumerate(p["layers"]):
        x = linear(lp, x)
        if i < n - 1 or final_activation:
            x = jax.nn.relu(x)
    return x

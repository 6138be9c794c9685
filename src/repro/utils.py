"""Small shared utilities: pytree helpers, dtype policy, rng streams, the
persistent compile cache."""
from __future__ import annotations

import functools
import os
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

Params = Any  # nested dict of jnp arrays
PyTree = Any


def tree_size(tree: PyTree) -> int:
    """Total number of elements across all leaves."""
    return sum(int(np.prod(x.shape)) for x in jax.tree.leaves(tree))


def tree_bytes(tree: PyTree) -> int:
    return sum(int(np.prod(x.shape)) * x.dtype.itemsize for x in jax.tree.leaves(tree))


def tree_cast(tree: PyTree, dtype) -> PyTree:
    return jax.tree.map(lambda x: x.astype(dtype) if jnp.issubdtype(x.dtype, jnp.floating) else x, tree)


def tree_zeros_like(tree: PyTree) -> PyTree:
    return jax.tree.map(jnp.zeros_like, tree)


def tree_add(a: PyTree, b: PyTree) -> PyTree:
    return jax.tree.map(jnp.add, a, b)


def tree_sub(a: PyTree, b: PyTree) -> PyTree:
    return jax.tree.map(jnp.subtract, a, b)


def tree_scale(a: PyTree, s) -> PyTree:
    return jax.tree.map(lambda x: x * s, a)


def tree_axpy(alpha, x: PyTree, y: PyTree) -> PyTree:
    """alpha * x + y"""
    return jax.tree.map(lambda xi, yi: alpha * xi + yi, x, y)


def tree_dot(a: PyTree, b: PyTree):
    leaves = jax.tree.map(lambda x, y: jnp.vdot(x.astype(jnp.float32), y.astype(jnp.float32)), a, b)
    return functools.reduce(jnp.add, jax.tree.leaves(leaves))


def tree_norm(a: PyTree):
    return jnp.sqrt(tree_dot(a, a))


def tree_allclose(a: PyTree, b: PyTree, rtol=1e-5, atol=1e-6) -> bool:
    oks = jax.tree.leaves(
        jax.tree.map(lambda x, y: bool(np.allclose(np.asarray(x, np.float64), np.asarray(y, np.float64),
                                                   rtol=rtol, atol=atol)), a, b))
    return all(oks)


def tree_max_abs_diff(a: PyTree, b: PyTree) -> float:
    diffs = jax.tree.leaves(jax.tree.map(
        lambda x, y: float(np.max(np.abs(np.asarray(x, np.float64) - np.asarray(y, np.float64)))) if x.size else 0.0,
        a, b))
    return max(diffs) if diffs else 0.0


def split_key_tree(key, tree: PyTree) -> PyTree:
    """One rng key per leaf, matching the tree structure."""
    leaves, treedef = jax.tree.flatten(tree)
    keys = jax.random.split(key, len(leaves))
    return jax.tree.unflatten(treedef, list(keys))


def has_nan(tree: PyTree) -> bool:
    return any(bool(jnp.isnan(x).any()) for x in jax.tree.leaves(tree)
               if jnp.issubdtype(x.dtype, jnp.floating))


def chunked(seq, n):
    for i in range(0, len(seq), n):
        yield seq[i:i + n]


COMPILE_CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
# <checkout>/.jax_cache (git-ignored): a fixed path, because the directory
# is part of what lets a later run find the entries an earlier one wrote
REPO_COMPILE_CACHE = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
        __file__)))), ".jax_cache")


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX has read it at import
    and the directory is not set here; otherwise the cache goes to
    :data:`REPO_COMPILE_CACHE` inside the checkout. Either way a cache key
    includes the program's metadata: JAX's default key strips it, and a
    program that differs from a cached one only in its ``op_name``s (the
    round's named scopes, ``repro.core.phases``) would load that one's
    executable, whose op names a profiler trace then shows. Call it
    before the first compile (the trainer's ``main`` and
    ``chip_smoke.py`` do)."""
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    if os.environ.get(COMPILE_CACHE_ENV):
        return os.environ[COMPILE_CACHE_ENV]
    jax.config.update("jax_compilation_cache_dir", REPO_COMPILE_CACHE)
    return REPO_COMPILE_CACHE

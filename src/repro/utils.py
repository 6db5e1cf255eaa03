"""Shared small utilities: initializers, losses, tree helpers.

Kept dependency-free (jax + numpy only) so every layer of the framework can
import it without cycles.
"""
from __future__ import annotations

import functools
import os
from pathlib import Path
from typing import Any, Callable, Mapping

import jax
import jax.numpy as jnp
import numpy as np

PyTree = Any


# ---------------------------------------------------------------------------
# Initializers
# ---------------------------------------------------------------------------

def glorot_uniform(key: jax.Array, shape: tuple[int, ...],
                   dtype=jnp.float32, in_axis: int = -2,
                   out_axis: int = -1) -> jax.Array:
    """Glorot/Xavier uniform. Works for >=2-D shapes."""
    fan_in = shape[in_axis]
    fan_out = shape[out_axis]
    limit = np.sqrt(6.0 / (fan_in + fan_out))
    return jax.random.uniform(key, shape, dtype, -limit, limit)


def normal_init(key: jax.Array, shape: tuple[int, ...], stddev: float,
                dtype=jnp.float32) -> jax.Array:
    return stddev * jax.random.normal(key, shape, dtype)


def truncated_normal_init(key: jax.Array, shape: tuple[int, ...],
                          stddev: float, dtype=jnp.float32) -> jax.Array:
    return stddev * jax.random.truncated_normal(key, -2.0, 2.0, shape, dtype)


def varying_axes(*like: Any) -> frozenset:
    """The manual mesh axes any leaf of ``like`` varies over — empty
    outside ``shard_map``. Kernel output shapes carry it (``pallas_call``
    under ``shard_map`` must be told how its outputs vary)."""
    axes = frozenset()
    for leaf in jax.tree.leaves(like):
        axes |= jax.typeof(leaf).vma
    return axes


def zeros_like_varying(shape: tuple[int, ...], dtype, *like: Any
                       ) -> jax.Array:
    """Zeros of ``shape`` that vary over every manual mesh axis any leaf
    of ``like`` varies over. Under ``shard_map`` a scan carry seeded from
    plain zeros is mesh-invariant while the carry the body returns varies
    with the shard's data or weights, and ``lax.scan`` refuses the
    mismatch; outside ``shard_map`` this is plain ``jnp.zeros``."""
    z = jnp.zeros(shape, dtype)
    axes = varying_axes(*like)
    return jax.lax.pcast(z, tuple(sorted(axes)), to="varying") \
        if axes else z


# ---------------------------------------------------------------------------
# Losses / metrics
# ---------------------------------------------------------------------------

def onehot(labels: jax.Array, num_classes: int, dtype=jnp.float32) -> jax.Array:
    return jax.nn.one_hot(labels, num_classes, dtype=dtype)


def softmax_cross_entropy(logits: jax.Array, labels: jax.Array) -> jax.Array:
    """Mean cross-entropy. ``labels`` are integer class ids (...,)."""
    logz = jax.nn.logsumexp(logits, axis=-1)
    label_logits = jnp.take_along_axis(
        logits, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(logz - label_logits)


def softmax_cross_entropy_masked(logits: jax.Array, labels: jax.Array,
                                 mask: jax.Array) -> jax.Array:
    """Token-masked mean cross-entropy (LM training).

    logits (..., V); labels (...,) int; mask (...,) {0,1}.
    """
    logz = jax.nn.logsumexp(logits, axis=-1)
    label_logits = jnp.take_along_axis(
        logits, labels[..., None], axis=-1)[..., 0]
    per_tok = (logz - label_logits) * mask
    denom = jnp.maximum(jnp.sum(mask), 1.0)
    return jnp.sum(per_tok) / denom


def accuracy(logits: jax.Array, labels: jax.Array) -> jax.Array:
    return jnp.mean((jnp.argmax(logits, axis=-1) == labels).astype(jnp.float32))


# ---------------------------------------------------------------------------
# Tree helpers
# ---------------------------------------------------------------------------

def tree_size(tree: PyTree) -> int:
    """Total number of elements across all leaves."""
    return sum(int(np.prod(x.shape)) for x in jax.tree.leaves(tree))


def tree_bytes(tree: PyTree) -> int:
    return sum(int(np.prod(x.shape)) * jnp.dtype(x.dtype).itemsize
               for x in jax.tree.leaves(tree))


def tree_zeros_like(tree: PyTree) -> PyTree:
    return jax.tree.map(jnp.zeros_like, tree)


def tree_add(a: PyTree, b: PyTree) -> PyTree:
    return jax.tree.map(jnp.add, a, b)


def tree_scale(tree: PyTree, s) -> PyTree:
    return jax.tree.map(lambda x: x * s, tree)


def tree_global_norm(tree: PyTree) -> jax.Array:
    leaves = [jnp.sum(jnp.square(x.astype(jnp.float32)))
              for x in jax.tree.leaves(tree)]
    return jnp.sqrt(jnp.sum(jnp.stack(leaves)))


def tree_cast(tree: PyTree, dtype) -> PyTree:
    return jax.tree.map(lambda x: x.astype(dtype), tree)


def flatten_dict(d: Mapping[str, Any], prefix: str = "",
                 sep: str = "/") -> dict[str, Any]:
    """Flatten a nested dict-of-dicts of arrays into {path: array}."""
    out: dict[str, Any] = {}
    for k, v in d.items():
        path = f"{prefix}{sep}{k}" if prefix else str(k)
        if isinstance(v, Mapping):
            out.update(flatten_dict(v, path, sep))
        else:
            out[path] = v
    return out


def unflatten_dict(flat: Mapping[str, Any], sep: str = "/") -> dict[str, Any]:
    out: dict[str, Any] = {}
    for path, v in flat.items():
        keys = path.split(sep)
        cur = out
        for k in keys[:-1]:
            cur = cur.setdefault(k, {})
        cur[keys[-1]] = v
    return out


# ---------------------------------------------------------------------------
# Misc
# ---------------------------------------------------------------------------

def ceil_div(a: int, b: int) -> int:
    return -(-a // b)


def round_up(a: int, b: int) -> int:
    return ceil_div(a, b) * b


def count_params(params: PyTree) -> int:
    return tree_size(params)


@functools.lru_cache(maxsize=None)
def cpu_device():
    return jax.devices("cpu")[0]


# ---------------------------------------------------------------------------
# Compilation cache (entry points only)
# ---------------------------------------------------------------------------

def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache for a program entry
    point (scripts, examples, benchmarks — never at library import).

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already keeps its
    cache there and nothing is set here. Otherwise the cache goes to
    ``.jax_cache/`` at the root of this checkout: a fixed path, because
    the path is part of each entry's key and a moving directory never
    hits. Returns the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(Path(__file__).resolve().parents[2] / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path

"""Weights from the seed, made by the benchmark and not by the program.

Every leaf, and every layer of a stacked leaf, draws from its own key
(seed → leaf path → layer), so the plain reference can regenerate one
block's weights alone.  The weights are bf16: N(0, 0.02²) for matrices and
N(0, 0.1²) for norm scales (the program's norms multiply by ``1 + scale``),
the dense model the quantizer is given.
"""

from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp


def base_key(seed: int):
    seed = abs(int(seed))
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF), (seed >> 32) & 0x7FFFFFFF)


def leaf_key(key, path: str, layer=0):
    """The key of one leaf's layer, from the seed's :func:`base_key`."""
    k = jax.random.fold_in(key, zlib.crc32(path.encode()) & 0x7FFFFFFF)
    return jax.random.fold_in(k, layer)


def is_norm(path: str) -> bool:
    return path.endswith("/scale") or path.endswith("/bias")


def dense_leaf(key, shape, dtype=jnp.bfloat16, norm=False):
    std = 0.1 if norm else 0.02
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


def _paths(tree, prefix=""):
    if isinstance(tree, dict):
        for k, v in tree.items():
            yield from _paths(v, f"{prefix}/{k}" if prefix else k)
    else:
        yield prefix, tree


def _set(tree: dict, path: str, value):
    keys = path.split("/")
    for k in keys[:-1]:
        tree = tree.setdefault(k, {})
    tree[keys[-1]] = value


def _stacked_dense(key, path, shape, dtype):
    keys = jax.vmap(lambda i: leaf_key(key, path, i))(jnp.arange(shape[0]))
    return jax.lax.map(lambda k: dense_leaf(k, shape[1:], dtype, is_norm(path)), keys)


def make_dense(shapes: dict, seed: int) -> dict:
    """The whole dense tree in one jitted call. ``shapes``: the program's
    ShapeDtypeStruct tree; leaves under ``dec`` carry a leading layer axis.
    The seed's key is an argument, not a constant of the program, so every
    seed runs the same compiled program and only the first run compiles it."""
    flat = list(_paths(shapes))

    def build(key):
        out: dict = {}
        for path, sds in flat:
            if path.startswith("dec/"):
                leaf = _stacked_dense(key, path, sds.shape, sds.dtype)
            else:
                leaf = dense_leaf(leaf_key(key, path), sds.shape, sds.dtype, is_norm(path))
            _set(out, path, leaf)
        return out

    return jax.jit(build)(base_key(seed))


def dense_block(shapes: dict, seed: int, layer: int, block: str = "b0") -> dict:
    """One block's dense leaves (the layer axis dropped), for the reference."""
    out = {}
    for path, sds in _paths(shapes["dec"][block], f"dec/{block}"):
        out[path.split("/", 2)[2]] = dense_leaf(
            leaf_key(base_key(seed), path, layer), sds.shape[1:], sds.dtype, is_norm(path))
    return out


def top_leaf(shapes: dict, seed: int, path: str):
    node = shapes
    for k in path.split("/"):
        node = node[k]
    return dense_leaf(leaf_key(base_key(seed), path), node.shape, node.dtype, is_norm(path))

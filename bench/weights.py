"""Weights and per-leaf readings made by the benchmark, never by the
program: drawn from the seed on the device in one jitted call, in the
layout of the program's parameter tree (a nested dict of arrays; the
leaves under ``blocks`` carry the layer on their leading axis).

The same seed gives the same weights, so the reference can draw them
again after the program's state is freed instead of keeping a copy.
"""
from __future__ import annotations

import zlib
from typing import Any, Dict, Iterator, Tuple

import numpy as np

Path = Tuple[str, ...]


def leaves(tree, prefix: Path = ()) -> Iterator[Tuple[Path, Any]]:
    """(path, leaf) of a nested dict, keys sorted."""
    if isinstance(tree, dict):
        for k in sorted(tree):
            yield from leaves(tree[k], prefix + (k,))
    else:
        yield prefix, tree


def rebuild(pairs) -> Dict:
    out: Dict = {}
    for path, leaf in pairs:
        node = out
        for p in path[:-1]:
            node = node.setdefault(p, {})
        node[path[-1]] = leaf
    return out


def seed_key(seed: int):
    """A JAX key from any whole number, 2**31 and past it included."""
    import jax
    a, b = np.random.SeedSequence(seed & (2 ** 64 - 1)).generate_state(2)
    return jax.random.fold_in(jax.random.key(int(a) >> 1), int(b) >> 1)


def _leaf(path: Path, shape, key):
    import jax
    import jax.numpy as jnp
    name = path[-1]
    normal = lambda: jax.random.normal(key, shape, jnp.float32)
    uniform = lambda lo, hi: jax.random.uniform(key, shape, jnp.float32,
                                                lo, hi)
    if name == "tok":                               # embedding (and head)
        return 0.02 * normal()
    if name in ("scale", "norm"):                   # RMSNorm gains
        return 1.0 + 0.1 * normal()
    if name in ("bq", "bk", "bv"):                  # q/k/v biases
        return 0.02 * normal()
    if name == "A_log":                             # decay rates in [1, 16]
        return jnp.log(uniform(1.0, 16.0))
    if name == "dt_bias":                           # softplus^-1(dt)
        dt = jnp.exp(uniform(np.log(1e-3), np.log(1e-1)))
        return dt + jnp.log(-jnp.expm1(-dt))
    if name == "D":
        return 1.0 + 0.1 * normal()
    # a matrix: fan-in is the second-to-last axis (the first is the layer)
    return normal() / np.sqrt(shape[-2])


def make(abstract, seed: int, dtype=None, shardings=None):
    """Weights for the tree ``abstract`` (ShapeDtypeStructs) from the
    seed, made on the device in one jitted call."""
    import jax
    import jax.numpy as jnp
    dtype = dtype or jnp.float32
    spec = [(p, tuple(s.shape)) for p, s in leaves(abstract)]

    def build(key):
        return rebuild(
            (p, _leaf(p, shp, jax.random.fold_in(
                key, zlib.crc32("/".join(p).encode()) & 0x7FFFFFFF)
            ).astype(dtype)) for p, shp in spec)

    return jax.jit(build, out_shardings=shardings)(seed_key(seed))


# ------------------------------------------------------------ readings
def leaf_norms(tree):
    """Per-leaf L2 norms, on the device; a leaf under ``blocks`` gives
    one norm per layer.  Returns a dict of arrays (path -> norms)."""
    import jax.numpy as jnp
    out = {}
    for p, x in leaves(tree):
        x = x.astype(jnp.float32)
        if p[0] == "blocks":
            out["/".join(p)] = jnp.sqrt(jnp.sum(
                jnp.square(x).reshape(x.shape[0], -1), axis=1))
        else:
            out["/".join(p)] = jnp.sqrt(jnp.sum(jnp.square(x)))[None]
    return out


def flat_norms(norms) -> Dict[str, float]:
    """Device norms -> {"path[layer]": float} on the host."""
    out = {}
    for k, v in norms.items():
        v = np.asarray(v, np.float64)
        if v.shape == (1,) and not k.startswith("blocks"):
            out[k] = float(v[0])
        else:
            out.update({f"{k}[{i}]": float(x) for i, x in enumerate(v)})
    return out


def change_norms(params, abstract, seed: int):
    """Per-leaf norms of ``params`` minus the seed's initial weights,
    drawn again inside the same call so no copy is kept."""
    import jax
    spec = [(p, tuple(s.shape)) for p, s in leaves(abstract)]

    def f(params, key):
        init = rebuild(
            (p, _leaf(p, shp, jax.random.fold_in(
                key, zlib.crc32("/".join(p).encode()) & 0x7FFFFFFF)))
            for p, shp in spec)
        return leaf_norms(jax.tree.map(lambda a, b: a.astype(b.dtype) - b,
                                       params, init))

    return jax.jit(f)(params, seed_key(seed))


def digest(tree):
    """Per-leaf wrapping sums of the raw bits: equal trees give equal
    digests whatever the order of the sum."""
    import jax
    import jax.numpy as jnp

    def bits(x):
        if x.dtype.itemsize == 4:
            u = jax.lax.bitcast_convert_type(x, jnp.uint32)
        elif x.dtype.itemsize == 2:
            u = jax.lax.bitcast_convert_type(x, jnp.uint16).astype(
                jnp.uint32)
        else:
            u = x.astype(jnp.uint32)
        # weight each bit pattern by its position, so a permutation shows
        w = jnp.arange(u.size, dtype=jnp.uint32).reshape(u.shape) | 1
        return jnp.stack([jnp.sum(u, dtype=jnp.uint32),
                          jnp.sum(u * w, dtype=jnp.uint32)])

    return jax.tree.map(bits, tree)

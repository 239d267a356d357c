"""Seeded weights and batches, made by the benchmark and handed to the
program and to the plain reference alike.

The program's own initialisers leave some layers at zero (AdaLN-Zero
gates, final projections, the last convolution of every block), and a
model whose output is identically zero agrees with any reference in any
precision. So every leaf is drawn here, from the seed, on the device, in
one jitted call: kernels at 1/sqrt(fan_in), biases small, norm scales
near one.
"""
from __future__ import annotations

import zlib

import numpy as np

SEED_MOD = 2 ** 31 - 1      # --seed may exceed 32 signed bits


def seed32(seed: int) -> int:
    return int(seed) % SEED_MOD


def _path_str(path) -> str:
    import jax
    return jax.tree_util.keystr(path)


def _fan_in(name: str, shape) -> int:
    if len(shape) == 3 and any(k in name for k in ("to_q", "to_k", "to_v")):
        return int(shape[0])            # DenseGeneral [C, heads, dim_head]
    return int(np.prod(shape[:-1])) if len(shape) > 1 else int(shape[0])


def fill_params(shapes, key):
    """A tree like `shapes` (ShapeDtypeStructs), every leaf seeded from
    `key` and the leaf's own path. Traceable: call it under `jax.jit`."""
    import jax
    import jax.numpy as jnp

    def leaf(path, s):
        name = _path_str(path)
        k = jax.random.fold_in(key, zlib.crc32(name.encode()) & 0x7FFFFFFF)
        z = jax.random.normal(k, s.shape, jnp.float32)
        if name.endswith("['kernel']"):
            out = z / np.sqrt(max(_fan_in(name, s.shape), 1))
        elif name.endswith("['scale']"):
            out = 1.0 + 0.1 * z
        else:
            out = 0.02 * z
        return out.astype(s.dtype)

    return jax.tree_util.tree_map_with_path(leaf, shapes)


def train_batches(seed: int, n: int, batch: int, resolution: int,
                  channels: int, tokens: int, features: int):
    """`n` host batches whose rows all differ: images in N(0,1) and a
    text context in N(0,1), as `bench.py` `make_batches` draws them."""
    rng = np.random.default_rng(seed32(seed))
    return [{
        "sample": rng.standard_normal(
            (batch, resolution, resolution, channels), dtype=np.float32),
        "cond": {"text": rng.standard_normal(
            (batch, tokens, features), dtype=np.float32)},
    } for _ in range(n)]


def null_context(tokens: int, features: int) -> np.ndarray:
    """The unconditional text context [1, tokens, features]: a stand-in
    for the encoder's embedding of the empty prompt. The same for every
    seed, as an encoder's would be: the train step closes over it, and a
    constant that moved with the seed would make every seed a new
    program to compile."""
    rng = np.random.default_rng(7919)
    return 0.5 * rng.standard_normal((1, tokens, features),
                                     dtype=np.float32)


def request_context(seed: int, index: int, tokens: int,
                    features: int) -> np.ndarray:
    """A request's pre-encoded conditioning [1, tokens, features]."""
    rng = np.random.default_rng([seed32(seed), 104729, int(index)])
    return rng.standard_normal((1, tokens, features), dtype=np.float32)

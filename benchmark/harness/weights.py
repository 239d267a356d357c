"""Seeded weights and batches, made by the benchmark and handed to the
program and to the plain reference alike.

The program's own initialisers leave some layers at zero (AdaLN-Zero
gates, final projections, the last convolution of every block), and a
model whose output is identically zero agrees with any reference in any
precision. So every leaf is drawn here, from the seed, on the device:
kernels at 1/sqrt(fan_in), biases small, norm scales near one; a subtree
at a time (`Maker`), or in one traceable call where the program wants an
`init_fn` (`fill_params`).
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
    if len(shape) == 3 and "expert" in name:
        return int(shape[1])            # stacked experts [experts, in, out]
    return int(np.prod(shape[:-1])) if len(shape) > 1 else int(shape[0])


def _fold(name: str) -> int:
    return zlib.crc32(name.encode()) & 0x7FFFFFFF


def _rule(name: str, shape):
    """How a leaf is scaled, from its path and shape: (kind, fan-in)."""
    if name.endswith("['kernel']"):
        return "kernel", max(_fan_in(name, shape), 1)
    return ("scale" if name.endswith("['scale']") else "other"), 1


def _draw(key, fold, shape, dtype, rule):
    import jax
    import jax.numpy as jnp
    z = jax.random.normal(jax.random.fold_in(key, fold), shape, jnp.float32)
    kind, fan_in = rule
    if kind == "kernel":
        out = z / np.sqrt(fan_in)
    elif kind == "scale":
        out = 1.0 + 0.1 * z
    else:
        out = 0.02 * z
    return out.astype(dtype)


def fill_params(shapes, key, prefix: str = ""):
    """A tree like `shapes` (ShapeDtypeStructs), every leaf seeded from
    `key` and the leaf's own path. Traceable: call it under `jax.jit`.
    A leaf depends on nothing but the key and its path, so a subtree
    filled alone, with the path down to it as `prefix`, holds the same
    values as in the whole tree."""
    import jax

    def leaf(path, s):
        name = prefix + _path_str(path)
        return _draw(key, _fold(name), s.shape, s.dtype,
                     _rule(name, s.shape))

    return jax.tree_util.tree_map_with_path(leaf, shapes)


class Maker:
    """The tree of `shapes` made a top-level subtree at a time, each in
    one jitted call, so that no more than one subtree's float32 normals
    are on the device beside what is already made. Subtrees of equal
    structure (the blocks of a transformer) share one compiled program:
    the leaves' path hashes are an operand. The values are
    `fill_params`'s, bit for bit. `widen` returns the leaves in float32
    after rounding them to the program's type: the plain reference's
    weights."""

    def __init__(self, shapes):
        self.shapes = shapes
        self._programs = {}
        self._parts = {}

    def names(self):
        return list(self.shapes)

    def _parts_of(self, name: str):
        import jax
        if name in self._parts:
            return self._parts[name]
        leaves, treedef = jax.tree_util.tree_flatten_with_path(
            self.shapes[name])
        paths = [f"['{name}']" + _path_str(p) for p, _ in leaves]
        sig = tuple((s.shape, str(s.dtype), _rule(n, s.shape))
                    for n, (_, s) in zip(paths, leaves))
        folds = np.asarray([_fold(n) for n in paths], np.uint32)
        self._parts[name] = treedef, sig, folds
        return self._parts[name]

    def program(self, name: str, widen: bool = False):
        """(the jitted maker of subtree `name`, its path hashes)."""
        import jax
        import jax.numpy as jnp
        treedef, sig, folds = self._parts_of(name)
        if (treedef, sig, widen) not in self._programs:
            def make(key, folds):
                out = [_draw(key, folds[i], shape, dtype, rule)
                       for i, (shape, dtype, rule) in enumerate(sig)]
                if widen:
                    out = [x.astype(jnp.float32) for x in out]
                return jax.tree_util.tree_unflatten(treedef, out)
            self._programs[(treedef, sig, widen)] = jax.jit(make)
        return self._programs[(treedef, sig, widen)], folds

    def make(self, key, names=None, widen: bool = False):
        """{name: subtree} for `names` (every top-level name if None)."""
        out = {}
        for name in (self.names() if names is None else names):
            fn, folds = self.program(name, widen)
            out[name] = fn(key, folds)
        return out

    def nbytes(self, names, widen: bool = False) -> int:
        import jax
        return int(sum(
            np.prod(s.shape) * (4 if widen else np.dtype(s.dtype).itemsize)
            for n in names
            for s in jax.tree_util.tree_leaves(self.shapes[n])))


def train_batches(seed: int, n: int, batch: int, resolution: int,
                  channels: int, tokens: int, features: int):
    """`n` host batches whose rows all differ: images in N(0,1) and a
    text context in N(0,1)."""
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

"""Core utilities: explicit PRNG threading, image transforms, tree helpers.

Capability parity with reference flaxdiff/utils.py (RandomMarkovState at
utils.py:93-98, clip/denormalize at 100-148, global-array assembly at
150-171), redesigned: RNG is an explicit `RngSeq` pytree usable inside jit,
and multi-host array assembly uses `jax.make_array_from_process_local_data`
instead of manual per-device splitting.
"""
from __future__ import annotations

import os
from typing import Optional, Tuple

import flax.struct
import jax
import jax.numpy as jnp
import numpy as np

from .typing import PRNGKey, PyTree


@flax.struct.dataclass
class RngSeq:
    """Functional RNG carrier — a pytree, safe to close over or carry in scan.

    Equivalent in capability to the reference's RandomMarkovState
    (flaxdiff/utils.py:93-98) but jit-native: `next_key` returns
    (new_state, key) without host round-trips.
    """

    key: PRNGKey

    @classmethod
    def create(cls, seed_or_key) -> "RngSeq":
        if isinstance(seed_or_key, int):
            return cls(key=jax.random.PRNGKey(seed_or_key))
        return cls(key=seed_or_key)

    def next_key(self) -> Tuple["RngSeq", PRNGKey]:
        new_key, sub = jax.random.split(self.key)
        return RngSeq(key=new_key), sub

    def next_keys(self, n: int) -> Tuple["RngSeq", PRNGKey]:
        keys = jax.random.split(self.key, n + 1)
        return RngSeq(key=keys[0]), keys[1:]

    def fold_in(self, data) -> "RngSeq":
        return RngSeq(key=jax.random.fold_in(self.key, data))


# Back-compat alias for code written against the reference naming.
RandomMarkovState = RngSeq


# <checkout>/.jax_cache, from this file's own location: the directory is
# part of what lets a second run find the first run's entries, so it is
# never derived from tempfile, a pid or a clock.
DEFAULT_COMPILATION_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    ".jax_cache")


def configure_compilation_cache(cache_dir: Optional[str] = None) -> str:
    """Turn on jax's persistent compilation cache; returns the directory
    in use. Call before the first compile of the process — jax decides
    once, at its first compile, whether the cache is in use.

    With JAX_COMPILATION_CACHE_DIR set, jax has already read it and this
    sets NO directory: whoever launched the process placed the cache
    (a chip harness, an operator) and an override would orphan it.
    Unset, the directory is `cache_dir` if given, else
    DEFAULT_COMPILATION_CACHE_DIR. Either way the size and time
    thresholds are zeroed so small programs (eval samplers, serving
    chunk programs) cache too."""
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        used = env_dir
    else:
        used = str(cache_dir) if cache_dir else DEFAULT_COMPILATION_CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", used)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return used


def normalize_images(x: jax.Array) -> jax.Array:
    """uint8 [0,255] -> float [-1,1] (reference: general_diffusion_trainer.py:258)."""
    return (x.astype(jnp.float32) - 127.5) / 127.5


def denormalize_images(x: jax.Array) -> jax.Array:
    """float [-1,1] -> uint8 [0,255] (reference: utils.py:100-148)."""
    return jnp.clip(x * 127.5 + 127.5, 0, 255).astype(jnp.uint8)


def clip_images(x: jax.Array, clip_min: float = -1.0, clip_max: float = 1.0) -> jax.Array:
    return jnp.clip(x, clip_min, clip_max)


def to_unit_float(images) -> "np.ndarray":
    """uint8 / [-1,1] / [0,1] / [0,255]-float images -> float32 [0, 1]
    (host-side numpy).

    One place for the range heuristic shared by metrics (FID feature
    input) and logging (grid PNGs), so the two can never disagree about a
    batch's range. Float ranges are detected by value: min < -0.01 means
    [-1,1]; max > 1.5 means [0,255] (un-normalized decode output); else
    already [0,1]."""
    import numpy as np
    images = np.asarray(images)
    if images.dtype == np.uint8:
        return images.astype(np.float32) / 255.0
    images = images.astype(np.float32)
    if images.min() < -0.01:       # [-1,1] convention
        images = (images + 1.0) / 2.0
    elif images.max() > 1.5:       # float [0,255] convention
        images = images / 255.0
    return np.clip(images, 0.0, 1.0)


def cfg_uncond_splice(emb: jax.Array, uncond: jax.Array,
                      uncond_mask: jax.Array) -> jax.Array:
    """CFG-dropout splice: where uncond_mask[b] is True, replace sample b's
    conditioning with the (broadcast) null embedding via jnp.where — the
    reference's correct masking semantics (inputs/__init__.py:122-137).

    Single source of truth for both the train step and input-config paths.
    """
    if uncond_mask.shape[0] != emb.shape[0]:
        raise ValueError(
            f"uncond_mask batch {uncond_mask.shape[0]} != "
            f"embedding batch {emb.shape[0]}")
    mask = uncond_mask.reshape((emb.shape[0],) + (1,) * (emb.ndim - 1))
    uncond_b = jnp.broadcast_to(uncond.astype(emb.dtype), emb.shape)
    return jnp.where(mask, uncond_b, emb)


def count_params(tree: PyTree) -> int:
    return sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(tree)
               if hasattr(x, "shape"))


def tree_bytes(tree: PyTree) -> int:
    return sum(x.size * x.dtype.itemsize for x in jax.tree_util.tree_leaves(tree)
               if hasattr(x, "dtype"))


def fill_params_by_path(template: PyTree, flat: dict, prefix: str = "",
                        label: str = "weight load") -> PyTree:
    """Fill `template`'s leaves from a '/'-path-keyed dict of arrays
    (optionally under `prefix`), matched by PATH with shape checking:
    every template leaf must be present and every prefixed key consumed,
    or a ValueError lists what's missing/mismatched/unused. Template
    leaves only need .shape/.dtype, so `jax.eval_shape` output works —
    no real init required. Shared by the InceptionV3 FID loader and the
    SD-VAE torch-weight loader."""
    sub = {k[len(prefix):]: v for k, v in flat.items()
           if k.startswith(prefix)}
    leaves_kp, treedef = jax.tree_util.tree_flatten_with_path(template)
    missing, mismatched, leaves = [], [], []
    for path, leaf in leaves_kp:
        key = "/".join(
            getattr(p, "key", getattr(p, "name", str(p))) for p in path)
        if key not in sub:
            missing.append(key)
            leaves.append(leaf)
            continue
        arr = sub.pop(key)
        if tuple(arr.shape) != tuple(leaf.shape):
            mismatched.append(f"{key}: file {arr.shape} vs model "
                              f"{tuple(leaf.shape)}")
            leaves.append(leaf)
            continue
        leaves.append(jnp.asarray(arr, dtype=leaf.dtype))
    errors = []
    if missing:
        errors.append(f"missing: {sorted(missing)[:5]}"
                      f"{' ...' if len(missing) > 5 else ''} "
                      f"({len(missing)} total)")
    if mismatched:
        errors.append(f"shape mismatches: {mismatched[:5]}")
    if sub:
        errors.append(f"unused keys: {sorted(sub)[:5]} ({len(sub)} total)")
    if errors:
        raise ValueError(
            f"{label} failed{f' under {prefix!r}' if prefix else ''} — "
            + "; ".join(errors))
    return jax.tree_util.tree_unflatten(treedef, leaves)


def form_global_array(path, array: np.ndarray, global_mesh: jax.sharding.Mesh,
                      axis_name: str = "data") -> jax.Array:
    """Assemble a host-local numpy batch shard into a global jax.Array.

    TPU-native replacement for the reference's manual per-device split +
    `make_array_from_single_device_arrays` (flaxdiff/utils.py:150-171,
    trainer/simple_trainer.py:43-65).
    """
    sharding = jax.sharding.NamedSharding(
        global_mesh, jax.sharding.PartitionSpec(axis_name))
    return jax.make_array_from_process_local_data(sharding, array)


def convert_to_global_tree(global_mesh: jax.sharding.Mesh, pytree: PyTree,
                           axis_name: str = "data") -> PyTree:
    return jax.tree_util.tree_map_with_path(
        lambda p, x: form_global_array(p, x, global_mesh, axis_name), pytree)


def serialize_model_config(name: str, config: dict) -> dict:
    """Flatten a model config for experiment tracking (reference utils.py:59-84)."""
    out = {"model_name": name}
    for k, v in config.items():
        if callable(v) and hasattr(v, "__name__"):
            out[k] = v.__name__
        elif isinstance(v, (list, tuple)):
            out[k] = list(v)
        else:
            out[k] = str(v) if not isinstance(v, (int, float, bool, str, dict, type(None))) else v
    return out

"""Active-mesh context: lets attention modules reach the device mesh.

Flax module trees are built from static config (strings, ints); a Mesh is
runtime state. The trainer/sampler declare the mesh once here and the
attention dispatch (`ops/attention.py` backend="ring") picks it up during
tracing — no mesh threading through every module constructor. This is the
TPU-native replacement for the reference's pattern of closing the mesh
over the train step (reference trainer/simple_trainer.py:176,413-415);
here any module can be sequence-parallel without its parent knowing.
"""
from __future__ import annotations

import contextlib
import contextvars
import math
from typing import Callable, Optional, Sequence, Tuple

import jax
from jax.sharding import Mesh, PartitionSpec

_active_mesh: contextvars.ContextVar[Optional[Mesh]] = \
    contextvars.ContextVar("flaxdiff_tpu_active_mesh", default=None)
_seq_axis: contextvars.ContextVar[str] = \
    contextvars.ContextVar("flaxdiff_tpu_seq_axis", default="seq")


def set_active_mesh(mesh: Optional[Mesh], seq_axis: str = "seq"):
    """Declare the mesh (and sequence axis name) model code should use.
    Returns nothing; call with None to clear."""
    _active_mesh.set(mesh)
    _seq_axis.set(seq_axis)


def get_active_mesh() -> Optional[Mesh]:
    return _active_mesh.get()


def get_seq_axis() -> str:
    return _seq_axis.get()


@contextlib.contextmanager
def use_mesh(mesh: Mesh, seq_axis: str = "seq"):
    """Scoped variant of set_active_mesh."""
    tok_m = _active_mesh.set(mesh)
    tok_s = _seq_axis.set(seq_axis)
    try:
        yield mesh
    finally:
        _active_mesh.reset(tok_m)
        _seq_axis.reset(tok_s)


def seq_parallel_active() -> bool:
    """True when a mesh with a >1-sized sequence axis is declared."""
    mesh = get_active_mesh()
    axis = get_seq_axis()
    return (mesh is not None and axis in mesh.axis_names
            and mesh.shape[axis] > 1)


def batch_shard_axes(mesh: Mesh, n_batch: int) -> Optional[Tuple[str, ...]]:
    """The >1-sized data-like axes (data x fsdp — matching
    mesh.batch_spec) a batch of `n_batch` rows shards over; None when
    the batch does not tile them."""
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    axes = tuple(a for a in ("data", "fsdp") if sizes.get(a, 1) > 1)
    if n_batch % math.prod(sizes[a] for a in axes) != 0:
        return None
    return axes


def batch_partition_entry(batch_axes: Sequence[str]):
    """The dim-0 PartitionSpec entry for `batch_axes`."""
    if len(batch_axes) > 1:
        return tuple(batch_axes)
    return batch_axes[0] if batch_axes else None


def per_device_over_batch(kernel_fn: Callable, operands: Sequence[jax.Array],
                          batched: Sequence[bool],
                          xla_fn: Callable) -> jax.Array:
    """Run an opaque (pallas_call) op on each device's batch shard.

    GSPMD cannot partition a Mosaic kernel: in a program compiled for
    more than one device, jax refuses to lower a pallas_call that is not
    inside a shard_map ("Mosaic kernels cannot be automatically
    partitioned" — seen on a 2x2 v5e, PR 21). shard_map makes the batch
    split explicit. `batched[i]` says operand i carries the batch on
    dim 0 (operand 0 must); the rest (per-channel scale/bias) are
    replicated, and shard_map's transpose psums their cotangents over
    the batch axes. Every output carries the batch on dim 0. On a mesh
    with no >1 batch axis (pure tensor/seq parallelism) the specs are
    fully replicated and every device runs the whole batch. With no
    active multi-device mesh this is `kernel_fn(*operands)`; a batch
    that does not tile the mesh takes `xla_fn(*operands)`, which GSPMD
    can partition."""
    mesh = get_active_mesh()
    if mesh is None or mesh.devices.size == 1:
        return kernel_fn(*operands)
    axes = batch_shard_axes(mesh, operands[0].shape[0])
    if axes is None:
        return xla_fn(*operands)
    spec = PartitionSpec(batch_partition_entry(axes))
    in_specs = tuple(spec if b else PartitionSpec() for b in batched)
    # check_vma off: pallas_call primitives carry no varying-axis info
    return jax.shard_map(kernel_fn, mesh=mesh, in_specs=in_specs,
                         out_specs=spec, check_vma=False)(*operands)

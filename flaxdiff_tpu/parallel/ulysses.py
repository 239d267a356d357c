"""Ulysses-style all-to-all sequence parallelism (DeepSpeed-Ulysses,
Jacobs et al. 2023; the "USP" alternative to ring attention).

The reference has no sequence parallelism (SURVEY §5.7). Where ring
attention rotates K/V shards around the mesh with n-1 `ppermute` hops,
Ulysses re-shards ONCE each way: sequence-sharded q/k/v become
head-sharded (every device sees the FULL sequence for its subset of
heads) via a single fused all_to_all, attention runs locally and
exactly, and one reverse all_to_all restores sequence sharding —
2 collectives total. Cheaper than the ring on all-to-all-friendly ICI
topologies when heads divide the axis; the ring wins when heads are too
few or K/V rotation can overlap compute.

The local attention never materializes the [S, S] score matrix: on TPU
it calls the first-party flash kernel, elsewhere a chunked online
softmax — so the long-sequence memory bound that justifies sequence
parallelism holds on every backend.

Requires: heads % axis_size == 0 (each device owns whole heads) and
seq % axis_size == 0. Exactness is verified against full attention in
tests/test_ulysses.py, gradients included.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from .ring_attention import seq_shard_spec

from jax import shard_map

_NEG = -1e30


def _local_attention(q, k, v, scale, chunk: int = 1024):
    """Exact attention over full-sequence local shards without an [S, S]
    materialization: the flash kernel on TPU, chunked online softmax
    elsewhere (O(S * chunk) live memory)."""
    from ..ops.attention import attention_backend_available

    if attention_backend_available("flash") and q.shape[1] >= 128:
        from ..ops.flash_attention import flash_attention
        d = q.shape[-1]
        pad = (-d) % 128
        if pad:
            widths = ((0, 0), (0, 0), (0, 0), (0, pad))
            out = flash_attention(jnp.pad(q, widths), jnp.pad(k, widths),
                                  jnp.pad(v, widths), scale=scale)
            return out[..., :d]
        return flash_attention(q, k, v, scale=scale)

    S = k.shape[1]
    chunk = min(chunk, S)
    pad = (-S) % chunk
    if pad:
        widths = ((0, 0), (0, pad), (0, 0), (0, 0))
        k = jnp.pad(k, widths)
        v = jnp.pad(v, widths)
    nb = k.shape[1] // chunk
    kb = k.reshape(k.shape[0], nb, chunk, *k.shape[2:]).swapaxes(0, 1)
    vb = v.reshape(v.shape[0], nb, chunk, *v.shape[2:]).swapaxes(0, 1)

    # Derive the zero-init carry from q so it inherits q's device-varying
    # axes (shard_map's varying-axis checker requires carry types to
    # match the body outputs exactly — same pattern as ring_attention).
    o0 = (q * 0).astype(jnp.float32)
    l0 = jnp.sum(o0, axis=-1).transpose(0, 2, 1)
    m0 = l0 + _NEG

    def body(carry, inp):
        o, l, m = carry
        kc, vc, idx = inp
        s = jnp.einsum("bqhd,bkhd->bhqk", q, kc,
                       preferred_element_type=jnp.float32) * scale
        kv_pos = idx * chunk + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 3)
        s = jnp.where(kv_pos < S, s, _NEG)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p, axis=-1)
        pv = jnp.einsum("bhqk,bkhd->bqhd", p, vc.astype(jnp.float32),
                        preferred_element_type=jnp.float32)
        o_new = o * corr.transpose(0, 2, 1)[..., None] + pv
        return (o_new, l_new, m_new), ()

    (o, l, _), _ = jax.lax.scan(body, (o0, l0, m0),
                                (kb, vb, jnp.arange(nb)))
    out = o / jnp.maximum(l.transpose(0, 2, 1)[..., None], 1e-30)
    return out.astype(q.dtype)


def ulysses_attention_sharded(q: jax.Array, k: jax.Array, v: jax.Array,
                              axis_name: str,
                              scale: Optional[float] = None) -> jax.Array:
    """Body to be called INSIDE shard_map: q/k/v are local sequence
    shards [B, S_local, H, D]. Returns the local output shard."""
    D = q.shape[-1]
    scale = scale if scale is not None else D ** -0.5

    # seq-sharded -> head-sharded in ONE fused all_to_all: stack q/k/v,
    # split the head dim across the axis, gather the full sequence.
    # [3, B, S/n, H, D] -> [3, B, S, H/n, D]
    qkv = jnp.stack([q, k, v])
    qkv = jax.lax.all_to_all(qkv, axis_name, split_axis=3, concat_axis=2,
                             tiled=True)
    o = _local_attention(qkv[0], qkv[1], qkv[2], scale)

    # head-sharded -> seq-sharded: the inverse re-shard (2nd collective)
    return jax.lax.all_to_all(o, axis_name, split_axis=1, concat_axis=2,
                              tiled=True)


def ulysses_self_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                           mesh: Mesh, seq_axis: str = "seq",
                           batch_axes: Tuple[str, ...] = ("data",),
                           scale: Optional[float] = None) -> jax.Array:
    """Top-level entry: [B, S, H, D] arrays, S sharded over `seq_axis`,
    B over `batch_axes`; heads and S must divide the axis size."""
    n = mesh.shape[seq_axis]
    if q.shape[2] % n != 0:
        raise ValueError(f"heads {q.shape[2]} not divisible by "
                         f"{seq_axis} axis size {n}")
    if q.shape[1] % n != 0:
        raise ValueError(f"sequence {q.shape[1]} not divisible by "
                         f"{seq_axis} axis size {n}")
    spec = seq_shard_spec(mesh, seq_axis, batch_axes)
    fn = shard_map(
        functools.partial(ulysses_attention_sharded, axis_name=seq_axis,
                          scale=scale),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec)
    return fn(q, k, v)

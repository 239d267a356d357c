"""Attention dispatch: first-party Pallas flash attention on TPU, XLA fallback.

Replaces the reference's call into JAX's prebuilt
`jax.experimental.pallas.ops.tpu.flash_attention` (reference
flaxdiff/models/attention.py:14-17,100-102) with a first-party kernel
(ops/flash_attention.py) and a `jax.nn.dot_product_attention` fallback for
CPU tests and shapes the kernel doesn't cover.

Layout convention: [batch, seq, heads, head_dim] (BTNH) everywhere.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np


def _flash_interpret() -> bool:
    """FLAXDIFF_FLASH_INTERPRET=1 routes flash dispatch through the
    Pallas interpreter on ANY platform — the debugging hook that runs
    the real kernel code paths inside full models on CPU (with
    ops.flash_attention._FORCE_LANES for the hardware lane layout)."""
    import os
    return os.environ.get("FLAXDIFF_FLASH_INTERPRET") == "1"


@functools.cache
def _flash_on_tpu() -> bool:
    # a backend that fails to initialise raises here: training on XLA
    # attention because the chip was missing must not look like success
    return jax.devices()[0].platform == "tpu"


def attention_backend_available(backend: str = "flash") -> bool:
    if backend == "prebuilt":
        from .prebuilt_flash import prebuilt_available
        return prebuilt_available()
    if backend != "flash":
        return True
    return _flash_on_tpu() or _flash_interpret()


def _flash_impl() -> str:
    """Which flash implementation backend="auto" uses on TPU:
    "firstparty" (ops/flash_attention.py, default) or "prebuilt" (JAX's
    tuned TPU kernel — the one the reference calls). Which is faster in
    a step: not measured (ROADMAP D2). Read at trace time, so
    multi-host runs must set it identically on every host."""
    import os
    return os.environ.get("FLAXDIFF_FLASH_IMPL", "firstparty")


def _self_mask(lq: int, lk: int, causal: bool, window: Optional[int]):
    """[lq, lk] boolean mask of a self-attention call, or None: query i
    sees keys j with j <= i (`causal`) and i - window < j (`window`)."""
    if not causal and window is None:
        return None
    assert lq == lk, "a causal / window mask is self-attention's"
    i = jnp.arange(lq)[:, None]
    j = jnp.arange(lk)[None, :]
    keep = jnp.ones((lq, lk), bool)
    if causal:
        keep = keep & (j <= i)
    if window is not None:
        keep = keep & (j > i - window)
    return keep


def _masked_call(q_heads: int, kv_heads: int, seq_len: int, causal: bool,
                 window: Optional[int]):
    """The ONE place the second head count and the mask are settled for
    both dispatchers: (special, window). A window the sequence never
    reaches is no window (static, so the kernel compiles without it);
    `special` says the call is grouped or masked, which only the flash
    kernel's BLHD entry and the XLA compositions serve."""
    if q_heads % kv_heads:
        raise ValueError(f"{q_heads} query heads are not a multiple of "
                         f"{kv_heads} key/value heads")
    if window is not None and window >= seq_len:
        window = None
    return bool(causal or window is not None or q_heads != kv_heads), window


def _xla_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                   scale: Optional[float] = None,
                   force_fp32_for_softmax: bool = True,
                   causal: bool = False,
                   window: Optional[int] = None,
                   key_mask: Optional[jax.Array] = None) -> jax.Array:
    """Plain XLA attention over [B, L, H, D]; softmax in f32 for bf16
    stability. `k` / `v` may carry fewer heads (query head i reads
    key/value head i // (H / KV)); `causal` / `window` as `_self_mask`;
    `key_mask` [B, Lq, Lk] (data): the keys each query reads, in every
    head, on top of them."""
    orig_dtype = q.dtype
    b, lq, h, d = q.shape
    kv = k.shape[2]
    scale = scale if scale is not None else 1.0 / jnp.sqrt(d).astype(jnp.float32)
    keep = _self_mask(lq, k.shape[1], causal, window)
    grouped = kv != h
    if grouped:     # [B, L, KV, G, D] queries beside [B, L, KV, D] keys
        q = q.reshape(b, lq, kv, h // kv, d)
    logits = jnp.einsum("bqkgd,bskd->bkgqs" if grouped else
                        "bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * scale
    if keep is not None:
        logits = jnp.where(keep, logits, -1e30)
    if key_mask is not None:
        logits = jnp.where(key_mask[:, None], logits, -1e30)
    if force_fp32_for_softmax:
        probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    else:
        probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bkgqs,bskd->bqkgd" if grouped else
                     "bhqk,bkhd->bqhd", probs.astype(orig_dtype), v)
    return out.reshape(b, lq, h, d) if grouped else out


def _flash_specs(mesh, n_batch: int, n_heads: int):
    """(batch_axes, head_axis) for shard-mapping flash attention over a
    multi-device mesh, or None when the shapes don't tile it.

    Batch shards over the data-like axes (data x fsdp — matching
    mesh.batch_spec), heads over the tensor axis (Megatron head-parallel
    attention). Everything else must stay unsharded inside the kernel.
    """
    from ..parallel.context import batch_shard_axes
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    head_axis = "tensor" if sizes.get("tensor", 1) > 1 else None
    if sizes.get("seq", 1) > 1:
        return None   # a >1 seq axis belongs to the ring backend
    batch_axes = batch_shard_axes(mesh, n_batch)
    if batch_axes is None:
        return None
    if head_axis and n_heads % sizes[head_axis] != 0:
        return None
    return batch_axes, head_axis


def _shard_map_qkv(body, mesh, spec):
    """shard_map over (q, k, v) with one spec; check_vma off because
    pallas_call primitives carry no varying-axis info."""
    return jax.shard_map(body, mesh=mesh, in_specs=(spec, spec, spec),
                         out_specs=spec, check_vma=False)


def _shard_mapped_flash(q: jax.Array, k: jax.Array, v: jax.Array,
                        scale: float, mesh, batch_axes, head_axis,
                        interpret: bool = False,
                        block_q: Optional[int] = None,
                        block_k: Optional[int] = None,
                        causal: bool = False,
                        window: Optional[int] = None) -> jax.Array:
    """Run the Pallas kernel per-device under shard_map.

    A pallas_call is opaque to GSPMD — in a program compiled for more
    than one device jax refuses to lower one outside a shard_map
    ("Mosaic kernels cannot be automatically partitioned"). shard_map
    makes the parallelism explicit:
    each device runs the kernel on its [b/dp, L, h/tp, d] shard; batch
    and head sharding need no collectives (to_out's contraction over
    sharded heads gets its all-reduce from GSPMD outside the kernel).
    """
    from .flash_attention import flash_attention

    from ..parallel.context import batch_partition_entry
    spec = jax.sharding.PartitionSpec(batch_partition_entry(batch_axes),
                                      None, head_axis, None)
    body = lambda a, b, c: flash_attention(a, b, c, scale, block_q,
                                           block_k, interpret, causal,
                                           window)
    return _shard_map_qkv(body, mesh, spec)(q, k, v)


def _shard_mapped_flash_bhld(q: jax.Array, k: jax.Array, v: jax.Array,
                             scale: float, mesh, batch_axes, head_axis,
                             interpret: bool = False,
                             block_q: Optional[int] = None,
                             block_k: Optional[int] = None) -> jax.Array:
    """_shard_mapped_flash for [B, H, L, D] operands: batch axes shard
    dim 0, the tensor axis shards heads on dim 1, and each device's
    local [b/dp, h/tp, L, d] shard reshapes FREELY into the kernel's
    [B*H, L, D] grid layout — multi-chip runs keep the transpose-free
    path the BHLD projections exist for (ADVICE r4: routing every
    multi-device mesh through the transposing BLHD dispatcher lost the
    layout win exactly on the production configs)."""
    from .flash_attention import flash_attention_bh

    from ..parallel.context import batch_partition_entry
    spec = jax.sharding.PartitionSpec(batch_partition_entry(batch_axes),
                                      head_axis, None, None)

    def body(ql, kl, vl):
        bl, hl = ql.shape[0], ql.shape[1]
        flat = lambda t: t.reshape(bl * hl, t.shape[2], t.shape[3])
        out = flash_attention_bh(flat(ql), flat(kl), flat(vl),
                                 scale=scale, block_q=block_q,
                                 block_k=block_k, interpret=interpret)
        return out.reshape(bl, hl, out.shape[1], out.shape[2])

    return _shard_map_qkv(body, mesh, spec)(q, k, v)


def _seq_parallel_gate(q: jax.Array, k: jax.Array,
                       need_head_divisible: bool = False):
    """(mesh, seq_axis) when sequence-parallel attention applies to these
    shapes under the active mesh, else None. Shared by the "ring" and
    "ulysses" dispatch branches so their gating can't drift apart."""
    from ..parallel.context import (get_active_mesh, get_seq_axis,
                                    seq_parallel_active)
    mesh = get_active_mesh()
    if not (seq_parallel_active() and q.shape[1] == k.shape[1]):
        return None
    seq_axis = get_seq_axis()
    data_n = int(np.prod([mesh.shape[a] for a in mesh.axis_names
                          if a == "data"])) if mesh else 1
    n = mesh.shape[seq_axis]
    if q.shape[1] % n != 0 or q.shape[0] % max(data_n, 1) != 0:
        return None
    if need_head_divisible and q.shape[2] % n != 0:
        return None
    return mesh, seq_axis


def dot_product_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                          backend: str = "auto",
                          scale: Optional[float] = None,
                          force_fp32_for_softmax: bool = True,
                          causal: bool = False,
                          window: Optional[int] = None) -> jax.Array:
    """Multi-head attention over BTNH tensors.

    `k` / `v` may carry fewer heads than `q`, a divisor of them (query
    head i reads key/value head i // (H / KV)); `causal` / `window`
    (self-attention): query i sees keys j with j <= i, and with
    i - window < j. A grouped or masked call runs the flash kernel or
    the XLA composition; the other backends serve neither and give way
    to "auto".

    backend: "flash" (Pallas TPU kernel), "xla", "ring" (sequence-parallel
    ring attention over the active mesh's seq axis — self-attention only),
    "ulysses" (all-to-all sequence parallelism: one re-shard each way,
    exact local attention; needs heads AND seq divisible by the seq axis),
    "performer" (FAVOR+ linear attention, O(L) approximate), or "auto"
    (flash on TPU when shapes qualify, else xla).
    """
    assert q.ndim == 4 and k.ndim == 4 and v.ndim == 4
    special, window = _masked_call(q.shape[2], k.shape[2], q.shape[1],
                                   causal, window)
    if special and backend in ("performer", "ring", "ulysses", "prebuilt"):
        backend = "auto"
    xla = functools.partial(
        _xla_attention, scale=scale, causal=causal, window=window,
        force_fp32_for_softmax=force_fp32_for_softmax)
    if backend == "performer":
        # softmax is implicit in the kernel estimator (always f32), so
        # force_fp32_for_softmax has no meaning here; scale is honored.
        from .linear_attention import favor_attention
        return favor_attention(q, k, v, scale=scale)
    if backend in ("ring", "ulysses"):
        # Shared sequence-parallel gate: a declared mesh with a real seq
        # axis; equal q/kv sequence lengths (the heuristic separating
        # self-attention from cross-attention's short unsharded kv); and
        # shapes that shard evenly — seq divisible by the seq axis,
        # batch by the data axes; Ulysses additionally needs whole heads
        # per device. Anything else degrades to "auto" so the model
        # definition stays valid on single-chip, on CPU tests, and at
        # levels whose token/head counts don't tile the mesh.
        gate = _seq_parallel_gate(q, k, need_head_divisible=(
            backend == "ulysses"))
        if gate is not None:
            mesh, seq_axis = gate
            if backend == "ulysses":
                from ..parallel.ulysses import ulysses_self_attention
                return ulysses_self_attention(
                    q, k, v, mesh, seq_axis=seq_axis, scale=scale)
            from ..parallel.ring_attention import ring_self_attention
            return ring_self_attention(
                q, k, v, mesh, seq_axis=seq_axis, scale=scale)
        backend = "auto"
    if backend == "prebuilt":
        if _prebuilt_usable():
            return _prebuilt_btnh(q, k, v, scale)
        _warn_prebuilt_fallback()
        backend = "xla"
    use_flash = False
    if backend in ("auto", "flash") and attention_backend_available("flash"):
        # Sequences shorter than one q block gain nothing from the kernel;
        # head_dim is lane-padded to 128 below, so any head size qualifies.
        use_flash = q.shape[1] >= 128
    if use_flash:
        from .flash_attention import flash_attention
        d = q.shape[-1]
        scale_eff = scale if scale is not None else 1.0 / (d ** 0.5)
        # On a >1-device mesh the kernel must be shard-mapped (GSPMD
        # cannot partition a Mosaic call); shapes that don't tile the
        # mesh fall back to partitionable XLA attention instead.
        # per-shape autotuner plan (None fields when inactive/uncached:
        # dispatch keeps the exact env/default behavior)
        from . import autotune as _autotune
        bq, bk, native = _autotune.dispatch_plan(
            q.shape[1], k.shape[1], d, q.dtype)
        from ..parallel.context import get_active_mesh
        mesh = get_active_mesh()
        if mesh is not None and mesh.devices.size > 1:
            # the key/value heads tile the tensor axis if the queries'
            # do, never the other way round
            sharded = _flash_specs(mesh, q.shape[0], k.shape[2])
            if sharded is None:
                return xla(q, k, v)
            q, k, v, pad = _maybe_pad_head_dim(q, k, v, native=native)
            out = _shard_mapped_flash(q, k, v, scale_eff, mesh, *sharded,
                                      interpret=_flash_interpret(),
                                      block_q=bq, block_k=bk,
                                      causal=causal, window=window)
            return out[..., :d] if pad else out
        if not special and _route_auto_to_prebuilt(backend):
            return _prebuilt_btnh(q, k, v, scale)
        q, k, v, pad = _maybe_pad_head_dim(q, k, v, native=native)
        out = flash_attention(q, k, v, scale_eff, bq, bk,
                              _flash_interpret(), causal, window)
        return out[..., :d] if pad else out
    if backend == "flash" and not attention_backend_available("flash"):
        raise _flash_unavailable()
    return xla(q, k, v)


def _flash_unavailable() -> RuntimeError:
    return RuntimeError(
        "backend='flash' needs a TPU (or FLAXDIFF_FLASH_INTERPRET=1); "
        f"the default device is {jax.devices()[0].platform!r}. Use "
        "backend='auto' to take XLA attention off-TPU.")


def _prebuilt_usable() -> bool:
    """Prebuilt kernel is dispatchable here: kernel importable, a real
    TPU backend, and NOT a >1-device mesh — like any pallas_call the
    prebuilt kernel is opaque to GSPMD, and unlike the first-party path
    it has no shard_map wrapper yet, so in a multi-device program jax
    would refuse to lower it."""
    if not attention_backend_available("prebuilt"):
        return False
    from ..parallel.context import get_active_mesh
    mesh = get_active_mesh()
    return mesh is None or mesh.devices.size <= 1


def _prebuilt_bhld(q, k, v, scale):
    """Shared pad→prebuilt-kernel→slice sequence over [B,H,L,D]
    operands — the single implementation behind every dispatch site so
    the padding/scale policy cannot drift between them.

    Unlike the first-party path, head_dim stays NATIVE when it is a
    sublane multiple (the reference calls this kernel at d=64 unpadded —
    reference flaxdiff/models/attention.py:100-102; 128-padding it here
    would double its head-dim compute and bias every head-to-head
    against it). Only a non-multiple-of-8 head_dim is padded up to the
    next sublane multiple."""
    from .prebuilt_flash import prebuilt_flash_attention_bhld
    d = q.shape[-1]
    scale_eff = scale if scale is not None else 1.0 / (d ** 0.5)
    pad = (-d) % 8
    if pad:
        widths = ((0, 0),) * (q.ndim - 1) + ((0, pad),)
        q, k, v = (jnp.pad(t, widths) for t in (q, k, v))
    out = prebuilt_flash_attention_bhld(q, k, v, scale=scale_eff)
    return out[..., :d] if pad else out


def _prebuilt_btnh(q, k, v, scale):
    """_prebuilt_bhld for [B,L,H,D] callers — the one place the layout
    adaptation lives."""
    out = _prebuilt_bhld(
        q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
        v.transpose(0, 2, 1, 3), scale)
    return out.transpose(0, 2, 1, 3)


def _route_auto_to_prebuilt(backend: str) -> bool:
    """Single gating policy for routing backend="auto" to the prebuilt
    kernel (shared by both layout dispatchers so they cannot diverge):
    opted in via FLAXDIFF_FLASH_IMPL=prebuilt, not under the interpret
    debugging hook (the prebuilt pallas_call exposes no interpret), and
    dispatchable here (TPU, single-device mesh)."""
    return (backend == "auto" and _flash_impl() == "prebuilt"
            and not _flash_interpret() and _prebuilt_usable())


def _warn_prebuilt_fallback():
    import warnings
    warnings.warn("backend='prebuilt' requested but the prebuilt TPU "
                  "kernel is unavailable here (no TPU, or a >1-device "
                  "mesh it cannot shard); falling back to XLA attention",
                  stacklevel=3)


def _maybe_pad_head_dim(q, k, v, native=None):
    """Zero-pad head_dim to a 128-lane multiple unless
    FLAXDIFF_FLASH_NATIVE_D=1 — or a per-shape autotuner plan
    (`native`) — lets the kernel take the true sub-128 dim (Mosaic
    masks the unused lanes). Padding is exact: padded dims contribute 0
    to logits (scale stays 1/sqrt(d_orig)) and 0 to the padded output
    channels, which the caller slices off. Returns (q, k, v, pad).
    Shared by BOTH dispatchers so the policy cannot drift between
    layouts. `native=None` keeps the pure env behavior; a plan-derived
    bool already has the env folded in (env wins inside the autotuner),
    so it is applied directly."""
    d = q.shape[-1]
    pad = (-d) % 128
    if pad and d % 8 == 0:
        if native is not None:
            if native:
                pad = 0
        else:
            import os
            if os.environ.get("FLAXDIFF_FLASH_NATIVE_D") == "1":
                pad = 0
    if pad:
        widths = ((0, 0),) * (q.ndim - 1) + ((0, pad),)
        q, k, v = (jnp.pad(t, widths) for t in (q, k, v))
    return q, k, v, pad


def _xla_attention_bhld(q, k, v, scale=None,
                        force_fp32_for_softmax=True):
    """Plain XLA attention over [B, H, L, D] operands."""
    orig_dtype = q.dtype
    d = q.shape[-1]
    scale = (scale if scale is not None
             else 1.0 / jnp.sqrt(d).astype(jnp.float32))
    logits = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * scale
    if force_fp32_for_softmax:
        probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    else:
        probs = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", probs.astype(orig_dtype), v)


def dot_product_attention_bhld(q: jax.Array, k: jax.Array, v: jax.Array,
                               backend: str = "auto",
                               scale: Optional[float] = None,
                               force_fp32_for_softmax: bool = True,
                               causal: bool = False,
                               window: Optional[int] = None
                               ) -> jax.Array:
    """Attention over [B, H, L, D] operands — the flash kernel's native
    grid layout, reached by FREE reshapes (B and H adjacent).

    The [B,L,H,D] dispatcher pays a materialized transpose per operand
    around the opaque pallas custom call (the r3 trace counted ~750
    layout-copy ops/step around `_to_bh`); a BHLD-projecting module
    (models/attention.py AttentionLayer bhld=True) avoids them
    entirely. Sequence-parallel / performer paths route through the
    BLHD dispatcher (one transpose each way — they were not the copy
    hotspot); single-device flash/XLA and multi-device batch/head-
    sharded flash (shard_map over the mesh) run natively."""
    assert q.ndim == 4 and k.ndim == 4 and v.ndim == 4
    b, h, lq, d = q.shape

    from ..parallel.context import get_active_mesh
    mesh = get_active_mesh()
    multi = mesh is not None and mesh.devices.size > 1
    special, window = _masked_call(h, k.shape[1], lq, causal, window)
    if special:
        # the kernel's [B*H, L, D] entry serves one head count and no
        # mask: a grouped or masked call pays the BLHD entry's transposes
        out = dot_product_attention(
            q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
            v.transpose(0, 2, 1, 3), backend=backend, scale=scale,
            force_fp32_for_softmax=force_fp32_for_softmax,
            causal=causal, window=window)
        return out.transpose(0, 2, 1, 3)
    if backend in ("ring", "ulysses", "performer") or multi:
        # batch/head-sharded flash keeps the BHLD-native shard_map path
        # (free reshapes into the kernel grid); everything else —
        # sequence-parallel backends, shapes that don't tile the mesh —
        # routes through the BLHD dispatcher (one transpose each way)
        if (multi and backend in ("auto", "flash")
                and attention_backend_available("flash") and lq >= 128):
            sharded = _flash_specs(mesh, b, h)
            if sharded is not None:
                scale_eff = scale if scale is not None else 1.0 / (d ** 0.5)
                from . import autotune as _autotune
                bq, bk, native = _autotune.dispatch_plan(
                    lq, k.shape[2], d, q.dtype)
                q, k, v, pad = _maybe_pad_head_dim(q, k, v, native=native)
                out = _shard_mapped_flash_bhld(
                    q, k, v, scale_eff, mesh, *sharded,
                    interpret=_flash_interpret(), block_q=bq, block_k=bk)
                return out[..., :d] if pad else out
        out = dot_product_attention(
            q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
            v.transpose(0, 2, 1, 3), backend=backend, scale=scale,
            force_fp32_for_softmax=force_fp32_for_softmax)
        return out.transpose(0, 2, 1, 3)

    if backend == "prebuilt":
        if _prebuilt_usable():
            return _prebuilt_bhld(q, k, v, scale)
        _warn_prebuilt_fallback()
        return _xla_attention_bhld(
            q, k, v, scale=scale,
            force_fp32_for_softmax=force_fp32_for_softmax)

    use_flash = (backend in ("auto", "flash")
                 and attention_backend_available("flash")
                 and lq >= 128)
    if not use_flash:
        if backend == "flash" and not attention_backend_available("flash"):
            raise _flash_unavailable()
        return _xla_attention_bhld(
            q, k, v, scale=scale,
            force_fp32_for_softmax=force_fp32_for_softmax)

    scale_eff = scale if scale is not None else 1.0 / (d ** 0.5)
    if _route_auto_to_prebuilt(backend):
        return _prebuilt_bhld(q, k, v, scale)

    from .flash_attention import flash_attention_bh
    from . import autotune as _autotune
    bq, bk, native = _autotune.dispatch_plan(lq, k.shape[2], d, q.dtype)
    q, k, v, pad = _maybe_pad_head_dim(q, k, v, native=native)
    q3 = q.reshape(b * h, q.shape[2], q.shape[3])
    k3 = k.reshape(b * h, k.shape[2], k.shape[3])
    v3 = v.reshape(b * h, v.shape[2], v.shape[3])
    out = flash_attention_bh(q3, k3, v3, scale=scale_eff,
                             block_q=bq, block_k=bk,
                             interpret=_flash_interpret())
    out = out.reshape(b, h, lq, out.shape[-1])
    return out[..., :d] if pad else out


def attend(q: jax.Array, k: jax.Array, v: jax.Array, *, bhld: bool = False,
           backend: str = "auto", scale: Optional[float] = None,
           force_fp32_for_softmax: bool = True, causal: bool = False,
           window: Optional[int] = None) -> jax.Array:
    """What an attention module calls: the dispatcher of its layout
    ([B, L, H, D], or [B, H, L, D] with `bhld`), with the second head
    count read off `k` and the mask handed on."""
    fn = dot_product_attention_bhld if bhld else dot_product_attention
    return fn(q, k, v, backend=backend, scale=scale,
              force_fp32_for_softmax=force_fp32_for_softmax,
              causal=causal, window=window)


def attend_selected(q: jax.Array, k: jax.Array, v: jax.Array,
                    key_mask: jax.Array, *,
                    k_shared: Optional[jax.Array] = None,
                    backend: str = "auto",
                    scale: Optional[float] = None) -> jax.Array:
    """Self-attention under a key mask that is data (a learned selection
    of keys, `ops/dsa.py`): `q`, `k`, `v` [B, H, Lp, D] -> [B, H, Lp, D];
    `key_mask` [B, L, L] (bool, L <= Lp) says which keys each query
    reads, in every head, and is the whole mask; `k_shared` [B, Lp, D]
    is a key part every head shares (`ops/flash_attention.py`
    `flash_attention_selected`). The kernel on a TPU (or under
    `FLAXDIFF_FLASH_INTERPRET=1`) from 128 tokens on, on one device; the
    XLA composition elsewhere (no `shard_map` carries the mask yet: no
    cell on more than one chip holds such a model)."""
    from .flash_attention import (_selected_composition,
                                  flash_attention_selected)
    from ..parallel.context import get_active_mesh
    mesh = get_active_mesh()
    one_device = mesh is None or mesh.devices.size <= 1
    if backend == "flash" and not attention_backend_available("flash"):
        raise _flash_unavailable()
    if (backend in ("auto", "flash") and one_device
            and key_mask.shape[1] >= 128
            and attention_backend_available("flash")):
        return flash_attention_selected(q, k, v, key_mask, k_shared, scale,
                                        None, None, _flash_interpret())
    return _selected_composition(q, k, v, key_mask, k_shared, scale)

"""First-party Pallas TPU flash attention: forward AND backward kernels.

Replaces the reference's dependency on JAX's prebuilt kernel
(reference flaxdiff/models/attention.py:14-17,100-102) with a fully
first-party implementation covering the whole autodiff path. Design:

- Forward: grid = (batch*heads, q_blocks, kv_blocks). Each program holds
  one q block in VMEM; the kv grid dimension streams k/v blocks from HBM
  through the Pallas pipeline (no whole-KV residency — VMEM use is
  O(block_q·d + block_k·d) regardless of sequence length). Running
  (max, sum, acc) live in VMEM scratch persisted across the innermost
  (sequential) grid dimension — classic online softmax, [Lq, Lk] is never
  materialized in HBM. The forward also emits per-row logsumexp,
  lane-replicated as [B*H, Lq, 128] f32 (the layout the TPU vector unit
  wants; same convention as JAX's prebuilt kernel residuals).
- Backward: two kernels. dq: grid (batch*heads, q_blocks, kv_blocks)
  accumulating dq over the kv dimension. dk/dv: grid (batch*heads,
  kv_blocks, q_blocks) accumulating over the q dimension. Both recompute
  probabilities blockwise from (q, k, lse) — O(N) memory, no stored probs.
  The per-row correction term delta = rowsum(dO * O) is computed ONCE as
  a fused XLA reduce before the kernels and streamed in lane-replicated
  like lse (computing it in-kernel cost an O-block HBM stream + VPU
  reduce per grid step in BOTH kernels).
- A key mask that is DATA (`flash_attention_selected`; a learned
  selection of keys, `ops/dsa.py`): the same forward kernel takes an int8
  mask block a tile, one mask a batch entry for all its heads, and, by
  scalar prefetch, each tile's count of read keys and the block whose
  copies serve it, so a tile that reads no key costs neither work nor
  copy. Its operands are head-major and may come padded to the blocks
  (`padded_length`), so that nothing is copied on the way in.
- kv-length masking via lane iota, so cross-attention (e.g. CLIP kv_len=77)
  works after padding to the lane-aligned block. Padded q rows are exact:
  zero-padded q gives finite lse, zero-padded dO zeroes their gradient
  contributions (no inf·0 NaNs).
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
LANES = 128

# Test hook: interpret mode normally shrinks the lane-replicated scratch
# to width 1, which skips the lane resize paths real TPU hits (a d<128
# native-head-dim bug lived there).
# Tests set this to LANES to run interpret with the hardware layout.
_FORCE_LANES: Optional[int] = None


def _bcast(x: jax.Array, width: int) -> jax.Array:
    """Resize a lane-replicated [rows, w] value to [rows, width] — every
    lane holds the same value, so slicing narrower (native head_dim < 128
    against the 128-lane scratch) is as exact as repeating wider."""
    w = x.shape[1]
    if w == width:
        return x
    if w == 1:
        return jnp.broadcast_to(x, (x.shape[0], width))
    if width < w:
        return x[:, :width]
    reps = -(-width // w)
    out = pltpu.repeat(x, reps, axis=1)
    return out if out.shape[1] == width else out[:, :width]


# ---------------------------------------------------------------------------
# Forward kernel
# ---------------------------------------------------------------------------

def _mask_block_range(qi, block_q: int, block_k: int, num_kb: int,
                      causal: bool, window: Optional[int]):
    """(first, last) kv block a q block needs under the causal / window
    mask (query i sees keys j with i - window < j <= i). Traced on `qi`;
    shared by the kernel's skip test and the k/v index maps, which clamp
    to it so that a skipped block costs no copy either."""
    last = num_kb - 1
    if causal:
        last = jnp.minimum(last, (qi * block_q + block_q - 1) // block_k)
    first = 0
    if window is not None:
        first = jnp.maximum(0, (qi * block_q - window + 1) // block_k)
    return first, last


def _fwd_kernel(*refs, scale: float, kv_len: int, block_k: int,
                group: int = 1, causal: bool = False,
                window: Optional[int] = None, mask_heads: int = 0,
                shared_key: bool = False):
    # refs = (q, k, v, o, lse?, m_scr, l_scr, acc_scr); lse is only
    # emitted on the custom_vjp fwd path — the plain primal skips the
    # residual write. With a key mask that is data (`mask_heads`, the
    # heads that share a batch entry's mask): (counts, fetch) scalar
    # prefetch first, the mask block after v and, with `shared_key`, the
    # key part every head shares after that.
    counts_ref = mask_ref = ks_ref = None
    if mask_heads:
        counts_ref, _, q_ref, k_ref, v_ref, mask_ref, *rest = refs
        if shared_key:
            ks_ref, *rest = rest
        o_ref, *rest = rest
    else:
        q_ref, k_ref, v_ref, o_ref, *rest = refs
    if len(rest) == 4:
        lse_ref, m_scr, l_scr, acc_scr = rest
    else:
        lse_ref, (m_scr, l_scr, acc_scr) = None, rest
    masked = causal or window is not None
    qi = pl.program_id(1) if masked or mask_heads else None
    ki = pl.program_id(2)
    num_kb = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def _block():
        q = q_ref[0]                            # [block_q, d] native dtype
        k = k_ref[0]                            # [block_k, d]
        v = v_ref[0]
        d = q.shape[-1]
        block_q = q.shape[-2]
        if shared_key:      # it lies in lanes the heads' own part leaves 0
            k = k + ks_ref[0]
        if group > 1:
            # the query heads that share this key/value head, stacked
            # on the rows: [group, block_q, d] -> [group * block_q, d]
            q = q.reshape(group * block_q, d)

        # bf16 x bf16 -> f32 rides the MXU natively; only the softmax
        # math is f32.
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if mask_heads:
            # the mask says everything: which keys each query reads, the
            # padding past either length among what it does not
            keep = mask_ref[0].astype(jnp.int32) != 0
        else:
            kv_idx = ki * block_k + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 1)
            keep = kv_idx < kv_len
        if masked:
            row = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            if group > 1:       # the row's position inside its head
                row = (row & (block_q - 1)
                       if block_q & (block_q - 1) == 0
                       else jax.lax.rem(row, block_q))
            q_idx = qi * block_q + row
            if causal:
                keep = jnp.logical_and(keep, kv_idx <= q_idx)
            if window is not None:
                keep = jnp.logical_and(keep, kv_idx > q_idx - window)
        s = jnp.where(keep, s, NEG_INF)

        m_prev = m_scr[...]                          # [rows, LANES]
        l_prev = l_scr[...]
        m_curr = jnp.max(s, axis=1, keepdims=True)   # [rows, 1]
        m_next = jnp.maximum(m_prev, m_curr)         # lane-replicated
        p = jnp.exp(s - _bcast(m_next, block_k))
        if masked or mask_heads:
            # a row whose every key so far is masked has m = NEG_INF and
            # exp(0) = 1 on each of them: they weigh nothing
            p = jnp.where(keep, p, 0.0)
        alpha = jnp.exp(m_prev - m_next)             # [rows, LANES]
        l_scr[...] = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
        m_scr[...] = m_next
        acc_scr[...] = (acc_scr[...] * _bcast(alpha, d)
                        + jax.lax.dot_general(
                            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32))

    if mask_heads:
        # a key block none of whose keys this query block reads is
        # skipped on the count the kernel was handed (its copies too: the
        # index maps follow `fetch` to the nearest block that is read)
        tile = ((pl.program_id(0) // mask_heads) * pl.num_programs(1)
                + qi) * num_kb + ki
        pl.when(counts_ref[tile] > 0)(_block)
    elif masked:
        # blocks wholly outside the mask are skipped (their copies too:
        # the index maps clamp to the same range)
        first, last = _mask_block_range(qi, o_ref.shape[-2], block_k,
                                        num_kb, causal, window)
        pl.when(jnp.logical_and(ki >= first, ki <= last))(_block)
    else:
        _block()

    @pl.when(ki == num_kb - 1)
    def _finalize():
        l = jnp.maximum(l_scr[...], 1e-30)
        d = o_ref.shape[-1]
        out = (acc_scr[...] * _bcast(1.0 / l, d)).astype(o_ref.dtype)
        o_ref[0] = out.reshape(o_ref.shape[1:]) if group > 1 else out
        if lse_ref is not None:
            lse_ref[0] = m_scr[...] + jnp.log(l)


# ---------------------------------------------------------------------------
# Backward kernels
# ---------------------------------------------------------------------------

def _bwd_dq_kernel(q_ref, k_ref, v_ref, g_ref, delta_ref, lse_ref,
                   dq_ref, dq_scr,
                   *, scale: float, kv_len: int, block_k: int):
    ki = pl.program_id(2)
    num_kb = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    q = q_ref[0]                                 # [block_q, d] native dtype
    k = k_ref[0]                                 # [block_k, d]
    v = v_ref[0]
    g = g_ref[0]                                 # [block_q, d]
    lse = lse_ref[0]                             # [block_q, LANES] f32
    # delta = rowsum(dO*O), computed ONCE host-side and lane-replicated
    # like lse — recomputing it per (qi, ki) grid step cost an extra
    # [block_q, d] O-block HBM stream plus VPU work in BOTH backward
    # kernels (VERDICT r4 #3: the duplicated s/p-side recompute)
    delta = delta_ref[0]                         # [block_q, LANES] f32

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    kv_idx = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    s = jnp.where(kv_idx < kv_len, s, NEG_INF)
    p = jnp.exp(s - _bcast(lse, block_k))

    dp = jax.lax.dot_general(g, v, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    ds = p * (dp - _bcast(delta, block_k)) * scale
    dq_scr[...] += jax.lax.dot_general(ds.astype(k.dtype), k,
                                       (((1,), (0,)), ((), ())),
                                       preferred_element_type=jnp.float32)

    @pl.when(ki == num_kb - 1)
    def _finalize():
        dq_ref[0] = dq_scr[...].astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, g_ref, delta_ref, lse_ref,
                    dk_ref, dv_ref, dk_scr, dv_scr,
                    *, scale: float, kv_len: int, block_k: int):
    qi = pl.program_id(2)
    num_qb = pl.num_programs(2)

    @pl.when(qi == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    ki = pl.program_id(1)
    q = q_ref[0]                                 # [block_q, d] native dtype
    k = k_ref[0]                                 # [block_k, d]
    v = v_ref[0]
    g = g_ref[0]                                 # [block_q, d]
    lse = lse_ref[0]                             # [block_q, LANES]
    delta = delta_ref[0]                         # [block_q, LANES]

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    kv_idx = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    s = jnp.where(kv_idx < kv_len, s, NEG_INF)
    p = jnp.exp(s - _bcast(lse, block_k))

    # dv += p^T @ g  (contract the q dimension)
    dv_scr[...] += jax.lax.dot_general(p.astype(g.dtype), g,
                                       (((0,), (0,)), ((), ())),
                                       preferred_element_type=jnp.float32)
    dp = jax.lax.dot_general(g, v, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    ds = p * (dp - _bcast(delta, block_k)) * scale
    dk_scr[...] += jax.lax.dot_general(ds.astype(q.dtype), q,
                                       (((0,), (0,)), ((), ())),
                                       preferred_element_type=jnp.float32)

    @pl.when(qi == num_qb - 1)
    def _finalize():
        dk_ref[0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)


# ---------------------------------------------------------------------------
# Host-side wrappers
# ---------------------------------------------------------------------------

def _pad_to(x: jax.Array, axis: int, multiple: int) -> jax.Array:
    size = x.shape[axis]
    pad = (-size) % multiple
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def _to_bh(x: jax.Array) -> jax.Array:
    """[B, L, H, D] -> [B*H, L, D]."""
    b, l, h, d = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b * h, l, d)


def _from_bh(x: jax.Array, b: int, h: int) -> jax.Array:
    bh, l, d = x.shape
    return x.reshape(b, h, l, d).transpose(0, 2, 1, 3)


DEFAULT_BLOCK_Q = 512
DEFAULT_BLOCK_K = 1024


def _block_sizes(lq: int, lk: int, block_q: Optional[int],
                 block_k: Optional[int], interpret: bool,
                 group: int = 1, masked: bool = False):
    """Effective block sizes. On TPU blocks stay lane-aligned (the caller
    pads head_dim; seq dims are padded here); in interpret mode small
    test shapes shrink the blocks instead.

    Defaults (block=None) are large — 512 q rows x 1024 kv rows, capped
    at the padded sequence — because per-program overhead dominated at
    128x128: the r3 trace showed the kernel at ~7% in-step MFU while the
    jax reference TPU kernel uses 512/1024 blocks for exactly this
    reason. Env overrides FLAXDIFF_FLASH_BLOCK_Q/K support on-chip
    A/B tuning without a rebuild."""
    import os
    rq = -(-lq // LANES) * LANES   # padded seq lengths
    rk = -(-lk // LANES) * LANES
    # env only fills the None default — an explicitly-passed block size
    # (tests, VMEM-bounded long-sequence callers) must win
    if block_q is None:
        env_q = os.environ.get("FLAXDIFF_FLASH_BLOCK_Q")
        # grouped queries: a block holds `group` heads' rows of it
        block_q = int(env_q) if env_q else min(
            max(LANES, DEFAULT_BLOCK_Q // group), rq)
    if block_k is None:
        env_k = os.environ.get("FLAXDIFF_FLASH_BLOCK_K")
        # under a mask whole blocks are skipped, so smaller ones skip
        # more: a lane's width up to 1024 keys, 512 beyond
        default_k = (DEFAULT_BLOCK_K if not masked
                     else LANES if rk <= 1024 else 512)
        block_k = int(env_k) if env_k else min(default_k, rk)
    if interpret:
        bq = min(block_q, max(lq, 8))
        bk = min(block_k, max(lk, 8))
    else:
        bq, bk = min(block_q, rq), min(block_k, rk)
    return bq, bk


def _mask_tiles(key_mask, lq_pad: int, lk_pad: int, bq: int, bk: int):
    """The key mask [B, Lq, Lk] (bool) as the kernel takes it: (int8
    [B, lq_pad, lk_pad], zeros in the padding; counts [B * nq * nk]
    int32, the keys each (query block, key block) tile reads; fetch, of
    the same shape: the key block whose copies serve a tile: its own
    where it reads any key, else the nearest read block before it in the
    query block's row, else the first after, so that a skipped tile
    moves nothing)."""
    b = key_mask.shape[0]
    m = jnp.pad(key_mask.astype(jnp.int8),
                ((0, 0), (0, lq_pad - key_mask.shape[1]),
                 (0, lk_pad - key_mask.shape[2])))
    nq, nk = lq_pad // bq, lk_pad // bk
    # keys first (the minor axis splits in place), then queries
    counts = jnp.sum(m.reshape(b, nq * bq, nk, bk), axis=3, dtype=jnp.int32)
    counts = jnp.sum(counts.reshape(b, nq, bq, nk), axis=2)
    ids = jnp.arange(nk, dtype=jnp.int32)
    live = counts > 0
    before = jax.lax.cummax(jnp.where(live, ids, -1), axis=2)
    after = jax.lax.cummin(jnp.where(live, ids, nk), axis=2, reverse=True)
    fetch = jnp.where(before >= 0, before, jnp.where(after < nk, after, 0))
    return m, counts.reshape(-1), fetch.reshape(-1)


def _fwd_impl(q3, k3, v3, scale, block_q, block_k, interpret,
              save_residuals: bool = False, causal: bool = False,
              window: Optional[int] = None, key_mask=None,
              k_shared=None):
    """Forward over [B*H, L, D] operands (the layout the kernel grids
    over natively — BHLD callers reach here with FREE reshapes, BLHD
    callers pay one transpose in _to_bh).

    Grouped queries: `q3` is [B*KV, G, L, D] beside [B*KV, L, D] keys
    and values, and a q block holds the G heads' rows, so a key/value
    block is read once for the heads that share it. `causal` / `window`
    (static): query i sees keys j with j <= i / i - window < j; blocks
    wholly outside the mask are skipped. With none of the three the
    kernel, its grid and its block maps are what they were before them
    (static Python branches). The call is named `fdt_flash_fwd` on the
    device, and `fdt_flash_fwd_window` where the window binds (it is
    shorter than the sequence).

    `key_mask` [B, Lq, Lk] (bool, DATA): which keys each query reads,
    one mask a batch entry shared by its B*H / B heads; it is the whole
    mask (`causal` / `window` off, one head count). The kernel is handed
    each tile's count of read keys ahead of the grid (scalar prefetch,
    as `ops/moe.py`'s `tile_group`) and skips the tiles that read
    none. `k_shared` [B, Lk, D]: a key part all of a batch entry's heads
    share, added to each head's key block in the kernel."""
    group = q3.shape[1] if q3.ndim == 4 else 1
    masked = causal or window is not None
    if key_mask is not None:
        assert group == 1 and not masked and not save_residuals, (
            "a key mask that is data is the whole mask of a call with "
            "one head count; its backward is the XLA composition's")
    bh, lq, d = q3.shape[0], q3.shape[-2], q3.shape[-1]
    kv_len = k3.shape[1]
    if scale is None:
        scale = 1.0 / (d ** 0.5)
    bq, bk = _block_sizes(lq, kv_len, block_q, block_k, interpret,
                          group, masked or key_mask is not None)
    lanes = _FORCE_LANES or (1 if interpret else LANES)

    qb = _pad_to(q3, q3.ndim - 2, bq)
    kb = _pad_to(k3, 1, bk)
    vb = _pad_to(v3, 1, bk)
    lq_pad, lk_pad = qb.shape[-2], kb.shape[1]
    num_kb = lk_pad // bk

    grid = (bh, lq_pad // bq, num_kb)
    if group > 1:
        q_block = (1, group, bq, d)
        q_map = lambda bh, qi, ki: (bh, 0, qi, 0)
    else:
        q_block = (1, bq, d)
        q_map = lambda bh, qi, ki: (bh, qi, 0)
    if masked:
        def kv_map(bh, qi, ki):
            first, last = _mask_block_range(qi, bq, bk, num_kb, causal,
                                            window)
            return (bh, jnp.clip(ki, first, last), 0)
    else:
        kv_map = lambda bh, qi, ki: (bh, ki, 0)
    out_specs = [pl.BlockSpec(q_block, q_map)]
    out_shape = [jax.ShapeDtypeStruct(qb.shape, q3.dtype)]
    if save_residuals:
        assert group == 1 and not masked, (
            "the masked / grouped call's backward is the XLA "
            "composition's: it keeps no residuals")
        out_specs.append(
            pl.BlockSpec((1, bq, lanes), lambda bh, qi, ki: (bh, qi, 0)))
        out_shape.append(
            jax.ShapeDtypeStruct((bh, lq_pad, lanes), jnp.float32))
    kernel_kwargs = dict(scale=scale, kv_len=kv_len, block_k=bk)
    if group > 1 or masked:
        kernel_kwargs.update(group=group, causal=causal, window=window)
    in_specs = [pl.BlockSpec(q_block, q_map),
                pl.BlockSpec((1, bk, d), kv_map),
                pl.BlockSpec((1, bk, d), kv_map)]
    scratch_shapes = [
        pltpu.VMEM((group * bq, lanes), jnp.float32),   # running max
        pltpu.VMEM((group * bq, lanes), jnp.float32),   # running sum
        pltpu.VMEM((group * bq, d), jnp.float32),       # accumulator
    ]
    operands, grid_args = (qb, kb, vb), dict(
        grid=grid, in_specs=in_specs, out_specs=out_specs,
        scratch_shapes=scratch_shapes)
    if key_mask is not None:
        heads, nq = bh // key_mask.shape[0], lq_pad // bq
        mask, counts, fetch = _mask_tiles(key_mask, lq_pad, lk_pad, bq, bk)
        kernel_kwargs.update(mask_heads=heads)

        def fetched(bh, qi, ki, fetch):
            return fetch[((bh // heads) * nq + qi) * num_kb + ki]

        read_map = lambda bh, qi, ki, counts, fetch: (
            bh, fetched(bh, qi, ki, fetch), 0)
        row_map = lambda bh, qi, ki, counts, fetch: (bh, qi, 0)
        in_specs = [pl.BlockSpec(q_block, row_map),
                    pl.BlockSpec((1, bk, d), read_map),
                    pl.BlockSpec((1, bk, d), read_map),
                    pl.BlockSpec((1, bq, bk),
                                 lambda bh, qi, ki, counts, fetch: (
                                     bh // heads, qi,
                                     fetched(bh, qi, ki, fetch)))]
        operands = (counts, fetch, qb, kb, vb, mask)
        if k_shared is not None:
            kernel_kwargs.update(shared_key=True)
            in_specs.append(pl.BlockSpec(
                (1, bk, d), lambda bh, qi, ki, counts, fetch: (
                    bh // heads, fetched(bh, qi, ki, fetch), 0)))
            operands += (_pad_to(k_shared, 1, bk),)
        grid_args = dict(grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=grid, in_specs=in_specs,
            out_specs=[pl.BlockSpec(q_block, row_map)],
            scratch_shapes=scratch_shapes))
    # a window that BINDS has a name of its own on the device, so that a
    # trace tells a windowed layer's time from a full layer's; one the
    # sequence never reaches reads every causal pair and keeps the name
    binds = window is not None and window < lq
    res = pl.pallas_call(
        functools.partial(_fwd_kernel, **kernel_kwargs),
        name="fdt_flash_fwd_window" if binds else "fdt_flash_fwd",
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        **grid_args,
    )(*operands)
    return (res[0], res[1]) if save_residuals else (res[0], None)



def _bwd_impl(q3, k3, v3, out_bh, lse, g3, scale, block_q, block_k,
              interpret):
    """Backward over [B*H, L, D] operands; returns 3-D dq/dk/dv."""
    bh, lq, d = q3.shape
    kv_len = k3.shape[1]
    if scale is None:
        scale = 1.0 / (d ** 0.5)
    bq, bk = _block_sizes(lq, kv_len, block_q, block_k, interpret)

    qb = _pad_to(q3, 1, bq)
    kb = _pad_to(k3, 1, bk)
    vb = _pad_to(v3, 1, bk)
    gb = _pad_to(g3, 1, bq)
    ob = _pad_to(out_bh, 1, bq)
    lq_pad, lk_pad = qb.shape[1], kb.shape[1]
    lanes = lse.shape[-1]

    # delta = rowsum(dO * O): one fused XLA elementwise-reduce over the
    # whole [bh, lq, d] tensors, lane-replicated like lse, instead of a
    # per-grid-step recompute inside both kernels (which also forced O
    # through HBM once per (qi, ki) pair in each kernel).
    delta = jnp.sum(gb.astype(jnp.float32) * ob.astype(jnp.float32),
                    axis=-1, keepdims=True)            # [bh, lq_pad, 1]
    delta = jnp.broadcast_to(delta, (bh, lq_pad, lanes))

    qkv_specs = [
        pl.BlockSpec((1, bq, d), lambda bh, qi, ki: (bh, qi, 0)),
        pl.BlockSpec((1, bk, d), lambda bh, qi, ki: (bh, ki, 0)),
        pl.BlockSpec((1, bk, d), lambda bh, qi, ki: (bh, ki, 0)),
        pl.BlockSpec((1, bq, d), lambda bh, qi, ki: (bh, qi, 0)),       # dO
        pl.BlockSpec((1, bq, lanes), lambda bh, qi, ki: (bh, qi, 0)),   # delta
        pl.BlockSpec((1, bq, lanes), lambda bh, qi, ki: (bh, qi, 0)),   # lse
    ]
    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, scale=scale, kv_len=kv_len,
                          block_k=bk),
        name="fdt_flash_bwd_dq",
        grid=(bh, lq_pad // bq, lk_pad // bk),
        in_specs=qkv_specs,
        out_specs=pl.BlockSpec((1, bq, d), lambda bh, qi, ki: (bh, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, lq_pad, d), q3.dtype),
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(qb, kb, vb, gb, delta, lse)

    # dk/dv: swap the roles of the q and kv grid dimensions.
    kv_specs = [
        pl.BlockSpec((1, bq, d), lambda bh, ki, qi: (bh, qi, 0)),
        pl.BlockSpec((1, bk, d), lambda bh, ki, qi: (bh, ki, 0)),
        pl.BlockSpec((1, bk, d), lambda bh, ki, qi: (bh, ki, 0)),
        pl.BlockSpec((1, bq, d), lambda bh, ki, qi: (bh, qi, 0)),       # dO
        pl.BlockSpec((1, bq, lanes), lambda bh, ki, qi: (bh, qi, 0)),   # delta
        pl.BlockSpec((1, bq, lanes), lambda bh, ki, qi: (bh, qi, 0)),   # lse
    ]
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, scale=scale, kv_len=kv_len,
                          block_k=bk),
        name="fdt_flash_bwd_dkv",
        grid=(bh, lk_pad // bk, lq_pad // bq),
        in_specs=kv_specs,
        out_specs=[
            pl.BlockSpec((1, bk, d), lambda bh, ki, qi: (bh, ki, 0)),
            pl.BlockSpec((1, bk, d), lambda bh, ki, qi: (bh, ki, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, lk_pad, d), k3.dtype),
            jax.ShapeDtypeStruct((bh, lk_pad, d), v3.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((bk, d), jnp.float32),
            pltpu.VMEM((bk, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(qb, kb, vb, gb, delta, lse)

    return dq[:, :lq], dk[:, :kv_len], dv[:, :kv_len]


def _to_bkv(q: jax.Array, kv_heads: int) -> jax.Array:
    """[B, L, H, D] -> [B*KV, H/KV, L, D]: query head i reads key/value
    head i // (H/KV), so a key/value head's queries are adjacent."""
    b, l, h, d = q.shape
    g = h // kv_heads
    return q.reshape(b, l, kv_heads, g, d).transpose(0, 2, 3, 1, 4).reshape(
        b * kv_heads, g, l, d)


def _from_bkv(x: jax.Array, b: int) -> jax.Array:
    bkv, g, l, d = x.shape
    return x.reshape(b, bkv // b, g, l, d).transpose(0, 3, 1, 2, 4).reshape(
        b, l, (bkv // b) * g, d)


def _plain(q, k, causal, window) -> bool:
    """Whether the call is the one from before the mask and the second
    head count: its forward and backward kernels are those."""
    return q.shape[2] == k.shape[2] and not causal and window is None


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    scale: Optional[float] = None,
                    block_q: Optional[int] = None,
                    block_k: Optional[int] = None,
                    interpret: bool = False,
                    causal: bool = False,
                    window: Optional[int] = None) -> jax.Array:
    """Flash attention over [B, L, H, D] tensors (full fwd+bwd in Pallas).

    head_dim must be a multiple of 8 on real TPU — multiples of 128 use
    full lanes; narrower dims are handled natively (Mosaic masks the
    sub-128 lanes) when the dispatch layer passes them through
    (FLAXDIFF_FLASH_NATIVE_D=1) and zero-padded to 128 otherwise.
    Sequence dims are padded internally. block_q/block_k default to
    large sequence-capped blocks (see _block_sizes).

    Grouped queries: `k` / `v` may carry fewer heads than `q`, a
    divisor of them; query head i reads key/value head i // (H / KV).
    `causal` / `window` (static; self-attention, equal lengths): query
    i sees keys j with j <= i, and with i - window < j. The forward is
    the same kernel (`fdt_flash_fwd`, static branches); the backward of
    a grouped or masked call is the XLA composition's
    (docs/KERNELS.md), not `fdt_flash_bwd_*`.

    The [B,L,H,D] layout pays a transpose into the kernel's native
    [B*H,L,D] grid layout on every operand — BHLD-projecting callers
    should use flash_attention_bh, whose reshapes are free (the r3
    trace counted ~750 layout-copy ops around these transposes).
    """
    return _forward(q, k, v, scale, block_q, block_k, interpret, causal,
                    window)


def _forward(q, k, v, scale, block_q, block_k, interpret, causal, window):
    b, lq, h, _ = q.shape
    if _plain(q, k, causal, window):
        out, _ = _fwd_impl(_to_bh(q), _to_bh(k), _to_bh(v), scale,
                           block_q, block_k, interpret)
        return _from_bh(out[:, :lq], b, h)
    if causal or window is not None:
        assert lq == k.shape[1], "a causal / window mask is self-attention's"
    kv = k.shape[2]
    assert h % kv == 0, f"{h} query heads over {kv} key/value heads"
    q3 = _to_bkv(q, kv) if kv != h else _to_bh(q)
    out, _ = _fwd_impl(q3, _to_bh(k), _to_bh(v), scale, block_q, block_k,
                       interpret, causal=causal, window=window)
    out = out[..., :lq, :]
    return _from_bkv(out, b) if kv != h else _from_bh(out, b, h)


def _fwd(q, k, v, scale, block_q, block_k, interpret, causal, window):
    if not _plain(q, k, causal, window):
        return _forward(q, k, v, scale, block_q, block_k, interpret,
                        causal, window), (q, k, v, None, None)
    out, lse = _fwd_impl(_to_bh(q), _to_bh(k), _to_bh(v), scale,
                         block_q, block_k, interpret,
                         save_residuals=True)
    b, lq, h, _ = q.shape
    return _from_bh(out[:, :lq], b, h), (q, k, v, out, lse)


def _bwd(scale, block_q, block_k, interpret, causal, window, res, g):
    q, k, v, out_bh, lse = res
    if lse is None:
        from .attention import _xla_attention
        _, vjp = jax.vjp(lambda q, k, v: _xla_attention(
            q, k, v, scale=scale, causal=causal, window=window), q, k, v)
        return vjp(g)
    b, _, h, _ = q.shape
    dq, dk, dv = _bwd_impl(_to_bh(q), _to_bh(k), _to_bh(v), out_bh, lse,
                           _to_bh(g), scale, block_q, block_k, interpret)
    return _from_bh(dq, b, h), _from_bh(dk, b, h), _from_bh(dv, b, h)


flash_attention.defvjp(_fwd, _bwd)


def padded_length(l: int) -> int:
    """`l` rounded up to what `flash_attention_selected` would pad a
    sequence of `l` tokens to at its default blocks."""
    bq, bk = _block_sizes(l, l, None, None, False, 1, True)
    both = bq * bk // math.gcd(bq, bk)
    return -(-l // both) * both


def _selected_composition(q, k, v, key_mask, k_shared, scale):
    """`flash_attention_selected` as the XLA composition."""
    from .attention import _xla_attention
    lk = key_mask.shape[-1]
    to_blhd = lambda a, n: a[:, :, :n].transpose(0, 2, 1, 3)
    k4 = to_blhd(k, lk)
    if k_shared is not None:
        k4 = k4 + k_shared[:, :lk, None]
    out = _xla_attention(to_blhd(q, key_mask.shape[1]), k4, to_blhd(v, lk),
                         scale=scale, key_mask=key_mask)
    out = out.transpose(0, 2, 1, 3)
    return _pad_to(out, 2, q.shape[2]) if out.shape[2] < q.shape[2] else out


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8))
def flash_attention_selected(q: jax.Array, k: jax.Array, v: jax.Array,
                             key_mask: jax.Array,
                             k_shared: Optional[jax.Array] = None,
                             scale: Optional[float] = None,
                             block_q: Optional[int] = None,
                             block_k: Optional[int] = None,
                             interpret: bool = False) -> jax.Array:
    """Self-attention under a key mask that is DATA: `q`, `k`, `v`
    [B, H, Lp, D] (the kernel's own layout: a free reshape away from
    [B*H, Lp, D]) -> [B, H, Lp, D].

    `key_mask` [B, L, L] (bool; a learned selection of keys,
    `ops/dsa.py`), L <= Lp: query i of batch entry b reads key j where
    `key_mask[b, i, j]`, in every head. It is the WHOLE mask (it holds
    the causal half if the call has one); the softmax runs over exactly
    those keys; a block of keys that a block of queries reads none of is
    skipped, copies and all, on per-tile counts taken from the mask and
    handed to the kernel ahead of its grid (scalar prefetch, as
    `ops/moe.py`'s `tile_group`). Rows L..Lp of the operands are
    padding nobody reads (the output's are zeros): a caller that makes
    its operands at a multiple of the blocks (`padded_length`) spares
    the kernel's own padding, a copy of each of them.
    `k_shared` [B, Lp, D]: a key part every head shares (a latent
    attention's one rotated part, in the lanes the heads' own parts
    leave zero), added in the kernel so that it is never copied once a
    head. The forward is `fdt_flash_fwd`; the backward is the XLA
    composition's (no cell trains it)."""
    return _selected(q, k, v, key_mask, k_shared, scale, block_q, block_k,
                     interpret)


def _selected(q, k, v, key_mask, k_shared, scale, block_q, block_k,
              interpret):
    b, h, lp, d = q.shape
    flat = lambda a: a.reshape(b * h, lp, d)
    out, _ = _fwd_impl(flat(q), flat(k), flat(v), scale, block_q, block_k,
                       interpret, key_mask=key_mask, k_shared=k_shared)
    return out[:, :lp].reshape(b, h, lp, d)


def _selected_fwd(q, k, v, key_mask, k_shared, scale, block_q, block_k,
                  interpret):
    return (_selected(q, k, v, key_mask, k_shared, scale, block_q, block_k,
                      interpret), (q, k, v, key_mask, k_shared))


def _selected_bwd(scale, block_q, block_k, interpret, res, g):
    q, k, v, key_mask, k_shared = res
    _, vjp = jax.vjp(lambda q, k, v, ks: _selected_composition(
        q, k, v, key_mask, ks, scale), q, k, v, k_shared)
    dq, dk, dv, dks = vjp(g)
    return dq, dk, dv, None, dks


flash_attention_selected.defvjp(_selected_fwd, _selected_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def flash_attention_bh(q: jax.Array, k: jax.Array, v: jax.Array,
                       scale: Optional[float] = None,
                       block_q: Optional[int] = None,
                       block_k: Optional[int] = None,
                       interpret: bool = False) -> jax.Array:
    """Flash attention over [B*H, L, D] tensors — the kernel's native
    grid layout. A BHLD attention module reshapes [B,H,L,D] here for
    FREE (B and H are adjacent), eliminating the per-operand transposes
    the [B,L,H,D] entry point pays."""
    out, _ = _fwd_impl(q, k, v, scale, block_q, block_k, interpret)
    return out[:, :q.shape[1]]


def _fwd_bh3(q, k, v, scale, block_q, block_k, interpret):
    out, lse = _fwd_impl(q, k, v, scale, block_q, block_k, interpret,
                         save_residuals=True)
    return out[:, :q.shape[1]], (q, k, v, out, lse)


def _bwd_bh3(scale, block_q, block_k, interpret, res, g):
    q, k, v, out_bh, lse = res
    return _bwd_impl(q, k, v, out_bh, lse, g, scale, block_q, block_k,
                     interpret)


flash_attention_bh.defvjp(_fwd_bh3, _bwd_bh3)

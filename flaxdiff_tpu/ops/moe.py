"""Routed experts: top-k routing and a grouped matrix product over the
experts THIS chip holds.

A layer of E_total routed experts is divided over chips by expert
parallelism; a chip holds `held` consecutive experts starting at
`first`. The router keeps its published width (scores over all E_total);
a pick of an expert held elsewhere contributes nothing here, in the
program and in the plain reference alike.

No token is dropped whatever the imbalance, at static shapes, with work
in proportion to the picks that land here:

- `dispatch` sorts the N x K picks by held expert into a buffer whose
  groups each start on a row tile (`TILE_M`): M = N*K + held*(TILE_M-1)
  rows rounded up to a tile, the worst case. A pick of an absent expert
  takes no row. `tile_group[i]` names tile i's expert and `num_tiles`
  how many tiles hold picks.
- `fdt_moe_gmm_gate_up` and `fdt_moe_gmm_down` (Pallas, TPU) run over
  `num_tiles` row tiles, a DYNAMIC grid bound, so the tiles past the
  last group cost nothing. A tile belongs to one expert, so no store is
  masked; the contraction runs whole in one step, so an expert's weight
  block stays in VMEM across the consecutive row tiles of its group and
  is read once per column tile. `gate_up` computes
  silu(x Wgate) * (x Wup) in one pass over x.
- Off the TPU the exact XLA composition runs: `jax.lax.ragged_dot` over
  the same buffer with the same (tile-padded) group sizes. On the chip
  the Pallas kernels shipped after an A/B against it IN the serving
  round program (docs/KERNELS.md, PERF.md PR 35); no switch is kept.
- The backward is the XLA composition's (a `custom_vjp`): not measured
  on the chip.

`routed_experts` carries a `custom_vmap`: tokens are routed
independently, so a `vmap` over rows (the serving round program's) pools
every row's tokens into ONE grouped product instead of one per row,
which would read every expert's weights once per row.
"""
from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

TILE_M = 128        # rows of a group's tile (the MXU's side on a v5e)
TILE_N = 256        # output columns of one grid step


def _on_tpu() -> bool:
    return jax.devices()[0].platform == "tpu"


# ---------------------------------------------------------------------------
# Routing
# ---------------------------------------------------------------------------

def route(h32: jax.Array, router_kernel: jax.Array, top_k: int,
          norm_topk_prob: bool = True,
          select_bias: Optional[jax.Array] = None,
          scale: float = 1.0) -> Tuple[jax.Array, jax.Array]:
    """Sigmoid routing over ALL the layer's experts: (idx [N, K] int32,
    weights [N, K] float32). The product and the sigmoid run in float32
    (`Precision.HIGHEST`: a v5e's default rounds float32 operands to
    bfloat16); the K largest scores, normalised over the K.

    `select_bias` [E] (`topk_method` `noaux_tc`: a held correction bias)
    moves which K are picked and nothing else: the K largest of score +
    bias, weighted by their SCORES. `scale` (`routed_scaling_factor`)
    multiplies the weights after the normalisation. Without either the
    call is what it was before them."""
    logits = jnp.dot(h32.astype(jnp.float32),
                     router_kernel.astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    scores = jax.nn.sigmoid(logits)
    if select_bias is None:
        vals, idx = jax.lax.top_k(scores, top_k)
    else:
        _, idx = jax.lax.top_k(scores + select_bias.astype(jnp.float32),
                               top_k)
        vals = jnp.take_along_axis(scores, idx, axis=-1)
    if norm_topk_prob:
        vals = vals / jnp.sum(vals, axis=-1, keepdims=True)
    if scale != 1.0:
        vals = vals * scale
    return idx.astype(jnp.int32), vals


def held_picks(idx: jax.Array, first: int, held: int
               ) -> Tuple[jax.Array, jax.Array]:
    """(local [N, K] int32, counts [held] int32): each pick's index among
    the experts held here (`held` itself for an absent expert's), and
    how many picks each held expert received."""
    local = idx - first
    here = (local >= 0) & (local < held)
    local = jnp.where(here, local, held)
    counts = jnp.sum(jax.nn.one_hot(local, held, dtype=jnp.int32),
                     axis=tuple(range(local.ndim)))
    return local, counts


def pick_counters(held, routed: int) -> Dict[str, int]:
    """The `moe/picks_*` telemetry counters a finished request adds
    (docs/OBSERVABILITY.md), from its held picks by layer and expert
    `held` [layers, experts] (host integers) and the token-picks
    `routed` its routers made over its evaluations, wherever the experts
    are (host arithmetic): the picks that landed here, and the largest
    expert's of each layer."""
    return {"moe/picks_routed": int(routed),
            "moe/picks_held": int(held.sum()),
            "moe/picks_hottest": int(held.max(axis=-1).sum())}


# ---------------------------------------------------------------------------
# Dispatch: picks -> a buffer of rows grouped by expert
# ---------------------------------------------------------------------------

def buffer_rows(n_picks: int, held: int, tile_m: int = TILE_M) -> int:
    """Rows of the grouped buffer: every pick held, every group's last
    tile padded."""
    rows = n_picks + held * (tile_m - 1)
    return -(-rows // tile_m) * tile_m


def dispatch(local: jax.Array, held: int, tile_m: int = TILE_M):
    """Where each pick goes in the grouped buffer.

    local [N, K]: the pick's held-expert index, `held` for an absent one.
    Returns (dest [N, K] int32: the pick's row, M for an absent expert's;
    src [M] int32: the token whose activations fill the row (0 for a
    padding row: finite values nobody reads); padded [held] int32: each
    group's rows, a multiple of `tile_m`; tile_group [M / tile_m] int32;
    num_tiles [] int32)."""
    n, k = local.shape
    m = buffer_rows(n * k, held, tile_m)
    flat = local.reshape(-1)
    onehot = jax.nn.one_hot(flat, held, dtype=jnp.int32)        # [P, held]
    sizes = jnp.sum(onehot, axis=0)
    # a pick's rank among its expert's picks, in token order
    rank = jnp.sum((jnp.cumsum(onehot, axis=0) - 1) * onehot, axis=1)
    padded = -(-sizes // tile_m) * tile_m
    ends = jnp.cumsum(padded)
    starts = ends - padded
    here = flat < held
    dest = jnp.where(here, starts[jnp.minimum(flat, held - 1)] + rank, m)
    token = jnp.arange(n * k, dtype=jnp.int32) // k
    src = jnp.zeros((m,), jnp.int32).at[dest].set(token, mode="drop")
    tile_start = jnp.arange(m // tile_m, dtype=jnp.int32) * tile_m
    tile_group = jnp.minimum(
        jnp.searchsorted(ends, tile_start, side="right"),
        held - 1).astype(jnp.int32)
    return (dest.reshape(n, k).astype(jnp.int32), src,
            padded.astype(jnp.int32), tile_group,
            (ends[-1] // tile_m).astype(jnp.int32))


# ---------------------------------------------------------------------------
# The grouped product
# ---------------------------------------------------------------------------

def _gate_up_kernel(tile_group, num_tiles, x_ref, wg_ref, wu_ref, o_ref):
    del tile_group, num_tiles
    x = x_ref[...]
    g = jnp.dot(x, wg_ref[...], preferred_element_type=jnp.float32)
    u = jnp.dot(x, wu_ref[...], preferred_element_type=jnp.float32)
    o_ref[...] = (g * jax.nn.sigmoid(g) * u).astype(o_ref.dtype)


def _down_kernel(tile_group, num_tiles, x_ref, w_ref, o_ref):
    del tile_group, num_tiles
    o_ref[...] = jnp.dot(x_ref[...], w_ref[...],
                         preferred_element_type=jnp.float32
                         ).astype(o_ref.dtype)


def _gmm_grid(x, weights, num_tiles, tile_m: int, tile_n: int,
              interpret: bool) -> dict:
    """The `pallas_call` arguments both kernels share: out[M, N] over
    `num_tiles` row tiles, tile i times the weights of expert
    `tile_group[i]`, every matrix of `weights` ([held, K, N]) entering
    the kernel as its [K, tile_n] block. Rows past the last tile are not
    written."""
    m, k = x.shape
    n = weights[0].shape[-1]
    tile_n = min(tile_n, n)
    assert m % tile_m == 0 and n % tile_n == 0, (m, n, tile_m, tile_n)

    def x_map(j, i, tile_group, num_tiles):
        return i, 0

    def w_map(j, i, tile_group, num_tiles):
        return tile_group[i], 0, j

    def o_map(j, i, tile_group, num_tiles):
        return i, j

    return dict(
        out_shape=jax.ShapeDtypeStruct((m, n), x.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            in_specs=[pl.BlockSpec((tile_m, k), x_map)]
            + [pl.BlockSpec((None, k, tile_n), w_map)] * len(weights),
            out_specs=pl.BlockSpec((tile_m, tile_n), o_map),
            # columns outermost: within one column tile the row tiles of
            # a group follow each other, so the group's weight block is
            # copied in once
            grid=(n // tile_n, num_tiles)),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=64 * 1024 * 1024),
        interpret=interpret)


def _expert_ffn_pallas(xs, wg, wu, wd, tile_group, num_tiles,
                       tile_m=TILE_M, tile_n=TILE_N, interpret=False):
    scalars = (tile_group, num_tiles.reshape(1))
    mid = pl.pallas_call(
        _gate_up_kernel, name="fdt_moe_gmm_gate_up",
        **_gmm_grid(xs, (wg, wu), num_tiles, tile_m, tile_n, interpret)
    )(*scalars, xs, wg, wu)
    return pl.pallas_call(
        _down_kernel, name="fdt_moe_gmm_down",
        **_gmm_grid(mid, (wd,), num_tiles, tile_m, tile_n, interpret)
    )(*scalars, mid, wd)


def _expert_ffn_xla(xs, wg, wu, wd, padded):
    """The exact XLA composition: `ragged_dot` over the same buffer with
    the same tile-padded group sizes (rows past their sum come out 0)."""
    dot = functools.partial(jax.lax.ragged_dot, group_sizes=padded,
                            preferred_element_type=jnp.float32)
    g, u = dot(xs, wg), dot(xs, wu)
    mid = (g * jax.nn.sigmoid(g) * u).astype(xs.dtype)
    return dot(mid, wd).astype(xs.dtype)


def _expert_ffn(xs, wg, wu, wd, padded, tile_group, num_tiles):
    if _on_tpu():
        return _expert_ffn_pallas(xs, wg, wu, wd, tile_group, num_tiles)
    return _expert_ffn_xla(xs, wg, wu, wd, padded)


@jax.custom_vjp
def expert_ffn(xs, wg, wu, wd, padded, tile_group, num_tiles):
    """down(silu(gate(x)) * up(x)) of every row of the grouped buffer by
    its group's expert: [M, D] -> [M, D]. Rows past the last group are
    unspecified (the kernels do not write them)."""
    return _expert_ffn(xs, wg, wu, wd, padded, tile_group, num_tiles)


def _expert_ffn_fwd(xs, wg, wu, wd, padded, tile_group, num_tiles):
    return (_expert_ffn(xs, wg, wu, wd, padded, tile_group, num_tiles),
            (xs, wg, wu, wd, padded))


def _expert_ffn_bwd(res, g):
    xs, wg, wu, wd, padded = res
    # rows past the groups hold whatever the kernel left: their
    # cotangent is nobody's
    live = jnp.arange(xs.shape[0]) < jnp.sum(padded)
    _, vjp = jax.vjp(lambda *a: _expert_ffn_xla(*a, padded), xs, wg, wu, wd)
    return vjp(jnp.where(live[:, None], g, 0).astype(xs.dtype)) + (
        None, None, None)


expert_ffn.defvjp(_expert_ffn_fwd, _expert_ffn_bwd)


# ---------------------------------------------------------------------------
# The routed part of a layer
# ---------------------------------------------------------------------------

def _routed(x, local, weights, wg, wu, wd):
    held = wg.shape[0]
    dest, src, padded, tile_group, num_tiles = dispatch(local, held)
    ys = expert_ffn(x[src], wg, wu, wd, padded, tile_group, num_tiles)
    here = (local < held)[..., None]
    picked = ys[jnp.minimum(dest, ys.shape[0] - 1)]             # [N, K, D]
    # select before the product: a row no group owns may hold anything
    picked = jnp.where(here, picked, 0).astype(jnp.float32)
    return jnp.sum(picked * weights[..., None], axis=1)


_pooled = jax.custom_batching.custom_vmap(_routed)


@_pooled.def_vmap
def _pooled_vmap(axis_size, in_batched, x, local, weights, wg, wu, wd):
    """Rows pooled: [R, N, ...] tokens are R*N tokens of one call. (A
    batch of WEIGHTS has no such reading and runs a call per entry.)"""
    args = [v if b else jnp.broadcast_to(v, (axis_size,) + v.shape)
            for v, b in zip((x, local, weights, wg, wu, wd), in_batched)]
    if any(in_batched[3:]):
        return jax.lax.map(lambda a: _pooled(*a), tuple(args)), True
    flat = [v.reshape((-1,) + v.shape[2:]) for v in args[:3]]
    y = _pooled(*flat, wg, wu, wd)
    return y.reshape((axis_size, -1) + y.shape[1:]), True


@jax.custom_vjp
def routed_experts(x, local, weights, wg, wu, wd):
    """sum over a token's picks held here of weight * expert(x):
    x [N, D], local [N, K] int32 (`held` for an absent expert's pick),
    weights [N, K] float32, wg / wu [held, D, F], wd [held, F, D]
    -> [N, D] float32. Under `vmap` the rows' tokens are pooled into one
    call; `custom_vmap` has no reverse mode, so the gradient is taken
    of the same function without it."""
    return _pooled(x, local, weights, wg, wu, wd)


def _routed_fwd(x, local, weights, wg, wu, wd):
    return (_pooled(x, local, weights, wg, wu, wd),
            (x, local, weights, wg, wu, wd))


def _routed_bwd(res, g):
    x, local, weights, wg, wu, wd = res
    _, vjp = jax.vjp(lambda x, w, *e: _routed(x, local, w, *e),
                     x, weights, wg, wu, wd)
    dx, dw, *de = vjp(g)
    return (dx, None, dw, *de)


routed_experts.defvjp(_routed_fwd, _routed_bwd)

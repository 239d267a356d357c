"""Routed experts: top-k routing and a grouped matrix product over the
experts THIS chip holds.

A layer of E_total routed experts is divided over chips by expert
parallelism; a chip holds `held` consecutive experts starting at
`first`. The router keeps its published width (scores over all E_total);
a pick of an expert held elsewhere contributes nothing here, in the
program and in the plain reference alike.

No token is dropped whatever the imbalance, at static shapes, and every
buffer and every pass is sized by the picks that land HERE, not by all
N x K of them:

- **The capacity.** A call of N tokens routes N x K picks, of which
  `held / total` land here when the routing is even. One pass serves
  `capacity` = ceil(SLACK x N x K x held / total) picks (SLACK = 2: the
  benchmark's two cells run it about half full), at most all N x K
  (`held == total`, a whole layer on one chip: one pass holds every
  pick).
- **Passes.** The held picks are numbered in TOKEN order (`held_order`:
  a pick's index in N x K, so a token's picks follow each other in the
  order of its k); pass p serves those numbered [p C, (p + 1) C):
  `dispatch` into a buffer of `buffer_rows(C, held)` rows, the two
  kernels, the combine into a float32 [N, D] accumulator. The passes are
  a loop with a traced bound, ceil(held picks / C): one as a rule, none
  where no pick landed (the result is zeros), more at an imbalance,
  exactly. A pick the FIRST pass served is "fitted": `routed_experts`
  returns each token's count, and `moe/picks_fitted / moe/picks_held`
  (docs/OBSERVABILITY.md) is 1.0 while the capacity holds.
- `dispatch` lays a pass's picks out by expert in a buffer whose groups
  each start on a row tile (`TILE_M`); a pick of an absent expert or of
  another pass takes no row. `tile_group[i]` names tile i's expert and
  `num_tiles` how many tiles hold picks.
- `fdt_moe_gmm_gate_up` and `fdt_moe_gmm_down` (Pallas, TPU) run over
  `num_tiles` row tiles, a DYNAMIC grid bound, so the tiles past the
  last group cost nothing. A tile belongs to one expert, so no store is
  masked; the contraction runs whole in one step, so an expert's weight
  block stays in VMEM across the consecutive row tiles of its group and
  is read once per column tile. `gate_up` computes
  act(x Wgate) * (x Wup) in one pass over x, `act` the model's own gate
  (static: `silu`, or `relu` for a ReGLU expert). The COLUMN tile is a
  rule of the call's shapes (`column_tile`: the contraction's depth k,
  the columns n, the matrices of the call, the element size): a grid
  step costs about 0.4 us beside its product, so a step takes all n
  columns where their blocks fit half the VMEM the call asks for (a
  shallow contraction: x is read once, a row tile is one step), and
  else the narrowest multiple of `TILE_N` whose product is `STEP_OPS`
  operations (docs/KERNELS.md has the arms read in the round programs,
  PR 47). `TILE_M` is not the rule's: it sizes the buffer, `dispatch`
  and the combine's tiles.
- **The combine** reads the rows that exist, not one for every one of
  the N x K picks: XLA gathers the pass's served rows in token order
  (standalone, at the memory's rate), and `fdt_moe_combine` (Pallas,
  TPU) adds them onto their tokens, a tile of `TILE_M` tokens and a
  chunk of `TILE_M` entries at a time: the MXU SELECTS every token's
  s-th entry (a 0/1 matrix x the chunk's rows: exact), the VPU adds
  weight x row to the accumulator, in place. It does no counted
  operation: the roofline's two kernels keep their names. It shipped
  over a scatter-add of the buffer's rows, a sorted scatter-add and a
  segmented sum by doubling after readings IN the round programs of both
  cells (docs/KERNELS.md).
- **A token's sum depends on the token alone.** Its picks' products are
  added to the accumulator ONE BY ONE in the order of its k, wherever
  its entries fall in a chunk, over chunks and over passes (token order
  numbers the passes for that), on the TPU and off it. So a request
  whose rows a round pools with other requests' comes out as it does
  alone, to the last bit (docs/SERVING.md's determinism contract), and a
  row that is not finite spoils its own token's sum and no other
  (`_entries`).
- Off the TPU the exact XLA composition runs: `jax.lax.ragged_dot` over
  the same buffer with the same (tile-padded) group sizes, in the same
  passes, and `_combine_xla`. On the chip the Pallas kernels shipped
  after A/Bs IN the serving round program (docs/KERNELS.md; PERF.md
  PR 35, PR 44); no switch is kept.
- The backward is the XLA composition's, of the one-pass form (a loop
  with a traced bound has no reverse mode; a `custom_vjp`): not measured
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
TILE_N = 256        # the narrowest column tile of a grid step
STEP_OPS = 200e6    # operations a grid step's product has at least
VMEM_LIMIT = 64 * 1024 * 1024       # what each kernel's call asks for
SLACK = 2           # a pass's capacity over the picks' even share


def _on_tpu() -> bool:
    return jax.devices()[0].platform == "tpu"


# ---------------------------------------------------------------------------
# Routing
# ---------------------------------------------------------------------------

def route(h32: jax.Array, router_kernel: jax.Array, top_k: int,
          norm_topk_prob: bool = True,
          select_bias: Optional[jax.Array] = None,
          scale: float = 1.0,
          weigh: str = "sigmoid") -> Tuple[jax.Array, jax.Array]:
    """Routing over ALL the layer's experts: (idx [N, K] int32, weights
    [N, K] float32). The product and the scores run in float32
    (`Precision.HIGHEST`: a v5e's default rounds float32 operands to
    bfloat16). `weigh` (static) is how the model states its scores:

    - `"sigmoid"`: a sigmoid of every logit; the K largest scores,
      normalised over the K (`norm_topk_prob`).
    - `"softmax_picked"`: the K largest LOGITS, weighted by the softmax
      over those K alone (they sum to 1 as they stand).

    `select_bias` [E] (`topk_method` `noaux_tc`: a held correction bias;
    sigmoid scores only) moves which K are picked and nothing else: the
    K largest of score + bias, weighted by their SCORES. `scale`
    (`routed_scaling_factor`) multiplies the weights last. A call that
    names none of them is what it was before them."""
    logits = jnp.dot(h32.astype(jnp.float32),
                     router_kernel.astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    if weigh == "softmax_picked":
        assert select_bias is None, "a selection bias corrects sigmoid scores"
        vals, idx = jax.lax.top_k(logits, top_k)
        vals = jax.nn.softmax(vals, axis=-1)
    else:
        assert weigh == "sigmoid", weigh
        scores = jax.nn.sigmoid(logits)
        if select_bias is None:
            vals, idx = jax.lax.top_k(scores, top_k)
        else:
            _, idx = jax.lax.top_k(
                scores + select_bias.astype(jnp.float32), top_k)
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


def pick_counters(held, routed: int, fitted) -> Dict[str, int]:
    """The `moe/picks_*` telemetry counters a finished request adds
    (docs/OBSERVABILITY.md), from its held picks by layer and expert
    `held` [layers, experts] (host integers), the token-picks `routed`
    its routers made over its evaluations, wherever the experts are
    (host arithmetic), and the held picks by layer the first pass served
    `fitted` [layers]: the picks that landed here, the largest expert's
    of each layer, and those a pass's capacity held."""
    return {"moe/picks_routed": int(routed),
            "moe/picks_held": int(held.sum()),
            "moe/picks_hottest": int(held.max(axis=-1).sum()),
            "moe/picks_fitted": int(fitted.sum())}


# ---------------------------------------------------------------------------
# Dispatch: a pass's picks -> a buffer of rows grouped by expert
# ---------------------------------------------------------------------------

def capacity(n_picks: int, held: int, total: int) -> int:
    """Picks one pass serves: `SLACK` times the share of a call's
    `n_picks` that lands on `held` of a layer's `total` experts when the
    routing is even, and never more than all of them (`held == total`:
    one pass holds every pick)."""
    return min(n_picks, -(-SLACK * n_picks * held // total))


def buffer_rows(n_picks: int, held: int, tile_m: int = TILE_M) -> int:
    """Rows of a grouped buffer that holds `n_picks` picks: every group's
    last tile padded."""
    rows = n_picks + held * (tile_m - 1)
    return -(-rows // tile_m) * tile_m


def held_order(local: jax.Array, held: int) -> jax.Array:
    """The held picks in token order: their indices into N*K ascending
    (a pick's index IS its token order, then its k), N*K past the last.

    local [N, K]: the pick's held-expert index, `held` for an absent one.
    One sort: it read 0.4-0.7 ms a turn under a scatter of N*K updates."""
    flat = local.reshape(-1)
    p = flat.shape[0]
    return jnp.sort(jnp.where(flat < held, jnp.arange(p, dtype=jnp.int32),
                              p))


def dispatch(local: jax.Array, picks: jax.Array, held: int,
             tile_m: int = TILE_M):
    """Where a pass's picks go in a grouped buffer of M =
    `buffer_rows(len(picks), held)` rows whose groups each start on a row
    tile.

    local [N, K]; picks [C] int32: the served picks' indices into N*K in
    token order, N*K for a slot nobody fills. Returns (rows [C] int32:
    each pick's row, M for such a slot; src [M] int32: the token whose
    activations fill the row (0 for a padding row: finite values nobody
    reads); padded [held] int32: each group's rows, a multiple of
    `tile_m`; tile_group [M / tile_m] int32; num_tiles [] int32)."""
    flat, k = local.reshape(-1), local.shape[-1]
    p, m = flat.shape[0], buffer_rows(picks.shape[0], held, tile_m)
    expert = jnp.where(picks < p, flat[jnp.minimum(picks, p - 1)], held)
    onehot = jax.nn.one_hot(expert, held, dtype=jnp.int32)      # [C, held]
    # a pick's rank among the pass's picks of its expert, in token order
    rank = jnp.sum((jnp.cumsum(onehot, axis=0) - 1) * onehot, axis=1)
    padded = -(-jnp.sum(onehot, axis=0) // tile_m) * tile_m
    ends = jnp.cumsum(padded)
    rows = jnp.where(picks < p,
                     (ends - padded)[jnp.minimum(expert, held - 1)] + rank,
                     m).astype(jnp.int32)
    src = jnp.zeros((m,), jnp.int32).at[rows].set(picks // k, mode="drop")
    tile_start = jnp.arange(m // tile_m, dtype=jnp.int32) * tile_m
    tile_group = jnp.minimum(
        jnp.searchsorted(ends, tile_start, side="right"),
        held - 1).astype(jnp.int32)
    return (rows, src, padded.astype(jnp.int32), tile_group,
            (ends[-1] // tile_m).astype(jnp.int32))


# ---------------------------------------------------------------------------
# The grouped product
# ---------------------------------------------------------------------------

def _gated(g, u, act: str):
    """act(gate) * up in float32: the model's own gate (`silu`, or
    `relu`: a ReGLU expert), static."""
    if act == "relu":
        return jnp.maximum(g, 0.0) * u
    assert act == "silu", act
    return g * jax.nn.sigmoid(g) * u


def _gate_up_kernel(tile_group, num_tiles, x_ref, wg_ref, wu_ref, o_ref, *,
                    act: str):
    del tile_group, num_tiles
    x = x_ref[...]
    g = jnp.dot(x, wg_ref[...], preferred_element_type=jnp.float32)
    u = jnp.dot(x, wu_ref[...], preferred_element_type=jnp.float32)
    o_ref[...] = _gated(g, u, act).astype(o_ref.dtype)


def _down_kernel(tile_group, num_tiles, x_ref, w_ref, o_ref):
    del tile_group, num_tiles
    o_ref[...] = jnp.dot(x_ref[...], w_ref[...],
                         preferred_element_type=jnp.float32
                         ).astype(o_ref.dtype)


def _gmm_vmem(k: int, tile_n: int, matrices: int, itemsize: int,
              tile_m: int = TILE_M) -> int:
    """Bytes of VMEM one grid step of a grouped product keeps: the
    weight blocks [k, tile_n], the x tile and the output tile, each
    double-buffered, and the float32 products before the cast."""
    return (2 * itemsize * (matrices * k * tile_n + tile_m * k
                            + tile_m * tile_n)
            + 4 * matrices * tile_m * tile_n)


def column_tile(k: int, n: int, matrices: int, itemsize: int,
                tile_m: int = TILE_M) -> int:
    """Output columns of one grid step of a grouped product [.., k] x
    `matrices` of [k, n], from the shapes alone.

    A step costs about 0.4 us beside its product (PR 47's fit of the two
    kernels at 2560/768 on a v5e), so (1) where the blocks of ALL n
    columns fit half the VMEM the call asks for, the step takes all of
    them: x is read once and a row tile is one step; else (2) the
    narrowest multiple of `TILE_N` dividing n whose product is
    `STEP_OPS` operations or more, or the widest that fits."""
    tiles = [t for t in range(TILE_N, n, TILE_N) if n % t == 0] + [n]
    fits = [t for t in tiles if _gmm_vmem(k, t, matrices, itemsize, tile_m)
            <= VMEM_LIMIT // 2] or tiles[:1]
    if fits[-1] == n:
        return n
    deep = [t for t in fits if 2 * tile_m * k * t * matrices >= STEP_OPS]
    return deep[0] if deep else fits[-1]


def _gmm_grid(x, weights, num_tiles, tile_m: int, tile_n: Optional[int],
              interpret: bool) -> dict:
    """The `pallas_call` arguments both kernels share: out[M, N] over
    `num_tiles` row tiles, tile i times the weights of expert
    `tile_group[i]`, every matrix of `weights` ([held, K, N]) entering
    the kernel as its [K, tile_n] block (`column_tile`'s, unless a test
    names one). Rows past the last tile are not written."""
    m, k = x.shape
    n = weights[0].shape[-1]
    if tile_n is None:
        tile_n = column_tile(k, n, len(weights), x.dtype.itemsize, tile_m)
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
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret)


def _expert_ffn_pallas(xs, wg, wu, wd, tile_group, num_tiles,
                       tile_m=TILE_M, tile_n=None, interpret=False,
                       act="silu"):
    scalars = (tile_group, num_tiles.reshape(1))
    mid = pl.pallas_call(
        functools.partial(_gate_up_kernel, act=act),
        name="fdt_moe_gmm_gate_up",
        **_gmm_grid(xs, (wg, wu), num_tiles, tile_m, tile_n, interpret)
    )(*scalars, xs, wg, wu)
    return pl.pallas_call(
        _down_kernel, name="fdt_moe_gmm_down",
        **_gmm_grid(mid, (wd,), num_tiles, tile_m, tile_n, interpret)
    )(*scalars, mid, wd)


def _expert_ffn_xla(xs, wg, wu, wd, padded, act="silu"):
    """The exact XLA composition: `ragged_dot` over the same buffer with
    the same tile-padded group sizes (rows past their sum come out 0)."""
    dot = functools.partial(jax.lax.ragged_dot, group_sizes=padded,
                            preferred_element_type=jnp.float32)
    g, u = dot(xs, wg), dot(xs, wu)
    mid = _gated(g, u, act).astype(xs.dtype)
    return dot(mid, wd).astype(xs.dtype)


def _expert_ffn(xs, wg, wu, wd, padded, tile_group, num_tiles, act):
    if _on_tpu():
        return _expert_ffn_pallas(xs, wg, wu, wd, tile_group, num_tiles,
                                  act=act)
    return _expert_ffn_xla(xs, wg, wu, wd, padded, act)


@functools.partial(jax.custom_vjp, nondiff_argnums=(7,))
def expert_ffn(xs, wg, wu, wd, padded, tile_group, num_tiles, act="silu"):
    """down(act(gate(x)) * up(x)) of every row of the grouped buffer by
    its group's expert: [M, D] -> [M, D]; `act` (static) is the model's
    gate, `silu` or `relu`. Rows past the last group are unspecified
    (the kernels do not write them)."""
    return _expert_ffn(xs, wg, wu, wd, padded, tile_group, num_tiles, act)


def _expert_ffn_fwd(xs, wg, wu, wd, padded, tile_group, num_tiles, act):
    return (_expert_ffn(xs, wg, wu, wd, padded, tile_group, num_tiles, act),
            (xs, wg, wu, wd, padded))


def _expert_ffn_bwd(act, res, g):
    xs, wg, wu, wd, padded = res
    # rows past the groups hold whatever the kernel left: their
    # cotangent is nobody's
    live = jnp.arange(xs.shape[0]) < jnp.sum(padded)
    _, vjp = jax.vjp(lambda *a: _expert_ffn_xla(*a, padded, act),
                     xs, wg, wu, wd)
    return vjp(jnp.where(live[:, None], g, 0).astype(xs.dtype)) + (
        None, None, None)


expert_ffn.defvjp(_expert_ffn_fwd, _expert_ffn_bwd)


# ---------------------------------------------------------------------------
# Combine: a pass's rows, weighted, back onto their tokens
# ---------------------------------------------------------------------------

def _entries(ys, picks, rows, weights):
    """A pass's served picks in token order as (token [C] int32, weight
    [C] float32, their rows of `ys` [C, D], live [C] bool, spoilt [C]
    bool). A slot nobody fills is not live: weight 0, the buffer's first
    row (which a group owns). A served row that is NOT FINITE is spoilt:
    its weight is NaN, and whoever sums takes the row for zeros, so that
    it spoils the whole of its own token's sum and nothing else (0 x inf
    in a product over entries would reach the tokens beside it)."""
    p, k = weights.size, weights.shape[-1]
    live = picks < p
    g = ys[jnp.where(live, rows, 0)]
    spoilt = ~jnp.all(jnp.isfinite(g), axis=1)
    w = jnp.where(live, weights.reshape(-1)[jnp.minimum(picks, p - 1)], 0)
    return (picks // k, jnp.where(live & spoilt, jnp.nan, w), g, live,
            spoilt)


def _finite(g):
    # through float32: Mosaic asks the test of a bfloat16 row for it
    return jnp.where(jnp.isfinite(g.astype(jnp.float32)), g, 0)


def _combine_xla(acc, ys, picks, rows, weights):
    """The exact XLA composition: the served rows gathered in token
    order and added onto their tokens one by one (a sorted scatter-add):
    a token's picks in the order of its k."""
    token, w, g, live, _ = _entries(ys, picks, rows, weights)
    g = jnp.where(live[:, None], _finite(g), 0).astype(jnp.float32)
    return acc.at[token].add(g * w[:, None], mode="drop",
                             indices_are_sorted=True)


def _combine_kernel(tile, chunk, first, rounds, spoilt, acc_ref, token_ref,
                    slot_ref, w_ref, g_ref, o_ref, finite_ref):
    v = pl.program_id(0)

    @pl.when(first[v] == 1)
    def _():
        o_ref[...] = acc_ref[...]

    tokens = tile[v] * TILE_M + jax.lax.broadcasted_iota(
        jnp.int32, (TILE_M, TILE_M), 0)
    mine = tokens == token_ref[...]         # [t, j]: entry j is token t's

    def add_rows_of(ref):
        def add(s, carry):
            # every token's s-th entry of the pass, if this chunk holds
            # it: at most one entry a token, so the product over entries
            # SELECTS (exactly: 1 x a row, 0 x finite rows), sums nothing
            sel = mine & (slot_ref[...] == s)
            one = jnp.where(sel, 1.0, 0.0)
            if ref.dtype == jnp.bfloat16:
                row = jnp.dot(one.astype(ref.dtype), ref[...],
                              preferred_element_type=jnp.float32)
            else:
                row = jnp.dot(one, ref[...].astype(jnp.float32),
                              precision=jax.lax.Precision.HIGHEST,
                              preferred_element_type=jnp.float32)
            weight = jnp.sum(jnp.where(sel, w_ref[...], 0.0), axis=1,
                             keepdims=True)
            o_ref[...] += weight * row
            return carry
        jax.lax.fori_loop(0, rounds[v], add, 0)

    @pl.when(spoilt[chunk[v]] == 0)
    def _():
        add_rows_of(g_ref)

    @pl.when(spoilt[chunk[v]] != 0)     # a chunk with a row not finite
    def _():
        finite_ref[...] = _finite(g_ref[...])
        add_rows_of(finite_ref)


def _combine_visits(token, slot, n: int):
    """The grid of `fdt_moe_combine`: one visit for every (tile of
    TILE_M tokens, chunk of TILE_M entries) that may share an entry, a
    token tile's visits consecutive and every tile visited.

    token [C] int32 ascending, C a multiple of TILE_M, entries past the
    last token's at a value no tile holds; slot [C] int32: an entry's
    place among its token's. Returns (tile [V], chunk [V], first [V]: 1
    on a tile's first visit, rounds [V]: the slots the visit's entries
    reach, num_visits []), all int32, V = N / TILE_M + C / TILE_M rounded
    up: what fits whatever the tokens' counts, of which `num_visits` are
    made."""
    tiles, chunks = -(-n // TILE_M), token.shape[0] // TILE_M
    bounds = jnp.searchsorted(
        token, jnp.arange(tiles + 1, dtype=jnp.int32) * TILE_M, side="left")
    lo = jnp.minimum(bounds[:-1] // TILE_M, chunks - 1)
    visits = jnp.maximum(-(-bounds[1:] // TILE_M) - lo, 1)
    ends = jnp.cumsum(visits)
    v = jnp.arange(tiles + chunks, dtype=jnp.int32)
    tile = jnp.minimum(jnp.searchsorted(ends, v, side="right"), tiles - 1)
    since = v - (ends - visits)[tile]
    chunk = jnp.minimum(lo[tile] + since, chunks - 1)
    met = token.reshape(chunks, TILE_M)[chunk] // TILE_M == tile[:, None]
    rounds = jnp.max(jnp.where(met, slot.reshape(chunks, TILE_M)[chunk] + 1,
                               0), axis=1)
    return (tile.astype(jnp.int32), chunk.astype(jnp.int32),
            (since == 0).astype(jnp.int32), rounds.astype(jnp.int32),
            ends[-1].astype(jnp.int32))


def _combine_pallas(acc, ys, picks, rows, weights, interpret=False):
    """`fdt_moe_combine` (Pallas, TPU): the served rows, gathered in
    token order by XLA (rows that exist, at the memory's rate), are added
    onto their tokens a tile of TILE_M tokens and a chunk of TILE_M
    entries at a time. The MXU only SELECTS: round s multiplies the 0/1
    matrix of every token's s-th entry by the chunk's rows, which copies
    at most one row a token exactly; the VPU then adds weight x row to
    the accumulator in float32. So a token's sum is its picks' products
    added ONE BY ONE in the order of its k, from the accumulator's value,
    wherever its entries fall in a chunk, over chunks and over passes:
    the sum `_combine_xla` makes, and the same whatever other tokens the
    call pools (docs/SERVING.md's determinism contract). A row that is
    not finite spoils its own token (`_entries`): its chunk's rows are
    taken for zeros where they are not finite, in that chunk alone."""
    n, d = acc.shape
    pad = -len(picks) % TILE_M
    picks = jnp.pad(picks, (0, pad), constant_values=weights.size)
    token, w, g, live, spoilt = _entries(ys, picks, jnp.pad(rows, (0, pad)),
                                         weights)
    token = jnp.where(live, token, jnp.iinfo(jnp.int32).max)
    # an entry's place among its token's: the entries before it that are
    # its token's follow each other (k - 1 shifted compares: a binary
    # search by entry read 1 ms a call in the round program)
    k = weights.shape[-1]
    before = jnp.pad(token, (k - 1, 0), constant_values=-1)
    slot = sum(((before[k - 1 - d:len(before) - d] == token).astype(jnp.int32)
                for d in range(1, k)), jnp.zeros_like(token))
    tile, chunk, first, rounds, num_visits = _combine_visits(token, slot, n)

    def by_chunk(width):
        return (lambda v, tile, chunk, first, rounds, spoilt:
                (chunk[v],) + (0,) * width)

    def by_tile(v, tile, chunk, first, rounds, spoilt):
        return tile[v], 0

    lanes = pl.BlockSpec((None, 1, TILE_M), by_chunk(2))
    return pl.pallas_call(
        _combine_kernel, name="fdt_moe_combine",
        out_shape=jax.ShapeDtypeStruct((n, d), jnp.float32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            in_specs=[pl.BlockSpec((TILE_M, d), by_tile), lanes, lanes,
                      lanes, pl.BlockSpec((TILE_M, d), by_chunk(1))],
            out_specs=pl.BlockSpec((TILE_M, d), by_tile),
            grid=(num_visits,),
            scratch_shapes=[pltpu.VMEM((TILE_M, d), g.dtype)]),
        # in place: a tile of `acc` is read on the tile's first visit,
        # before anything is written to it
        input_output_aliases={5: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
    )(tile, chunk, first, rounds,
      jnp.any(spoilt.reshape(-1, TILE_M), axis=1).astype(jnp.int32), acc,
      token.reshape(-1, 1, TILE_M), slot.reshape(-1, 1, TILE_M),
      w.reshape(-1, 1, TILE_M), g)


def _combine(acc, ys, picks, rows, weights):
    if _on_tpu():
        return _combine_pallas(acc, ys, picks, rows, weights)
    return _combine_xla(acc, ys, picks, rows, weights)


# ---------------------------------------------------------------------------
# The routed part of a layer
# ---------------------------------------------------------------------------

def _routed(x, local, weights, wg, wu, wd, total, one_pass=False,
            act="silu"):
    """(sum over a token's held picks of weight * expert(x) [N, D]
    float32, the token's picks the first pass served [N] int32), in
    passes of `capacity` picks each; `one_pass`: a pass holds every pick
    and combines by the XLA composition, with no loop: the form the
    backward differentiates."""
    (n, k), held = local.shape, wg.shape[0]
    count = n * k if one_pass else capacity(n * k, held, total)
    combine = _combine_xla if one_pass else _combine
    order = held_order(local, held)
    # pass p serves the held picks numbered [p C, (p + 1) C) in token
    # order: a token's picks meet the accumulator in the order of its k
    # whatever the passes
    slots = jnp.pad(order, (0, count), constant_values=n * k)

    def serve(p, acc):
        picks = jax.lax.dynamic_slice(slots, (p * count,), (count,))
        rows, src, padded, tile_group, num_tiles = dispatch(
            local, picks, held)
        ys = expert_ffn(x[src], wg, wu, wd, padded, tile_group, num_tiles,
                        act)
        return combine(acc, ys, picks, rows, weights)

    acc = jnp.zeros(x.shape, jnp.float32)
    if one_pass:
        acc = serve(0, acc)
    else:       # as many passes as the held picks take: 0, 1, ...
        passes = -(-jnp.sum(order < n * k, dtype=jnp.int32) // count)
        acc = jax.lax.fori_loop(0, passes, serve, acc)
    # the first pass serves the held picks up to the count-th
    fitted = (local.reshape(-1) < held) & (
        jnp.arange(n * k, dtype=jnp.int32) <= order[count - 1])
    return acc, jnp.sum(fitted.reshape(n, k), axis=1, dtype=jnp.int32)


@functools.lru_cache(maxsize=None)
def _pooled(total: int, act: str = "silu"):
    """`_routed` of a layer of `total` experts gated by `act` under a
    `custom_vmap` that pools rows: [R, N, ...] tokens are R*N tokens of
    one call. (A batch of WEIGHTS has no such reading and runs a call
    per entry.)"""
    @jax.custom_batching.custom_vmap
    def pooled(x, local, weights, wg, wu, wd):
        return _routed(x, local, weights, wg, wu, wd, total, act=act)

    @pooled.def_vmap
    def _(axis_size, in_batched, x, local, weights, wg, wu, wd):
        args = [v if b else jnp.broadcast_to(v, (axis_size,) + v.shape)
                for v, b in zip((x, local, weights, wg, wu, wd), in_batched)]
        if any(in_batched[3:]):
            return jax.lax.map(lambda a: pooled(*a), tuple(args)), (True,
                                                                     True)
        flat = [v.reshape((-1,) + v.shape[2:]) for v in args[:3]]
        return tuple(v.reshape((axis_size, -1) + v.shape[1:])
                     for v in pooled(*flat, wg, wu, wd)), (True, True)

    return pooled


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def routed_experts(x, local, weights, wg, wu, wd, total, act="silu"):
    """sum over a token's picks held here of weight * expert(x), and how
    many of the token's held picks the first pass served (all of them
    while the capacity holds):
    x [N, D], local [N, K] int32 (`held` for an absent expert's pick),
    weights [N, K] float32, wg / wu [held, D, F], wd [held, F, D],
    `total` the layer's experts over all chips, `act` the experts' gate
    (`silu` or `relu`; both static)
    -> ([N, D] float32, [N] int32). Under `vmap` the rows' tokens are
    pooled into one call; `custom_vmap` and the loop over passes have no
    reverse mode, so the gradient is taken of the one-pass form."""
    return _pooled(total, act)(x, local, weights, wg, wu, wd)


def _routed_fwd(x, local, weights, wg, wu, wd, total, act):
    return (_pooled(total, act)(x, local, weights, wg, wu, wd),
            (x, local, weights, wg, wu, wd))


def _routed_bwd(total, act, res, g):
    x, local, weights, wg, wu, wd = res
    _, vjp = jax.vjp(
        lambda x, w, *e: _routed(x, local, w, *e, total, one_pass=True,
                                 act=act)[0],
        x, weights, wg, wu, wd)
    dx, dw, *de = vjp(g[0])
    return (dx, None, dw, *de)


routed_experts.defvjp(_routed_fwd, _routed_bwd)

"""Pallas TPU kernel: PQTopK — fused RecJPQ scoring + running top-k.

Problem: serving a RecJPQ catalogue today materialises the full
``scores [B, N]`` (repro/kernels/jpq_scores) and then runs top-k over
it — at N = 10⁶ that is the inference bottleneck the PQTopK paper
("Efficient Inference of Sub-Item Id-based Sequential Recommendation
Models with Millions of Items") removes.  This kernel consumes the
partial-score LUT ``P [B, m, b]`` and the codebook ``codes [N, m]`` in
``[block_n]``-sized item tiles and keeps only a running ``(values,
ids)`` top-k per query, so the ``[B, N]`` tensor never exists in HBM.

Per tile (same MXU formulation and operand layout as jpq_scores,
``tile_scores``): the ``[Nt]`` codes tile becomes ``m`` one-hot
matrices contracted against the LUT, giving the tile scores
``S [Bt, Nt]`` in registers/VMEM; padding columns (N not a multiple of
block_n) are masked to −inf against the *global* item position; then
the tile is folded into the running list (``merge_tile`` below).
One-hot picks are exact (three bf16 parts of the LUT, one exact
product each), so fused scores are bit-identical to the gather
reference.  Signed zeros:
the public entrypoints (``ops.jpq_topk`` / ``ops.jpq_topk_lut``)
canonicalise ``-0.0 → +0.0`` in the LUT before it reaches any backend
— the one-hot MXU dot flattens ``-0.0`` to ``+0.0`` (−0.0 + 0.0 =
+0.0) while a gather keeps the sign, and ``lax.top_k``'s IEEE total
order ranks +0.0 above −0.0, so without canonicalisation the backends
could disagree on signed-zero ties.  With it, a zero score is +0.0 in
every backend and ±0.0 ties resolve by the id tie-break, identical to
the materialise reference over the canonicalised LUT (the scores are
numerically unchanged: −0.0 == +0.0).

Grid: ``(B/Bt, N/Nt)`` with the item dim innermost and *sequential*
("arbitrary" semantics): the output blocks are revisited at every item
step — ``index_map (i, n) -> (i, 0)`` — so the running list lives in
VMEM across the whole item sweep and is initialised under
``pl.when(n == 0)``.

Merge (Mosaic has no in-kernel ``top_k``, sort or gather): the running
list is an UNORDERED set of the k best (value, id) pairs seen so far
under the total order "value desc, then id asc".  Per tile, a
``while_loop`` extracts the tile's best remaining candidate per row
(max value, lowest id among equal values) and, where it beats the
set's worst entry (min value, highest id among equal values), writes
it over that entry; the loop stops when no row's best candidate gets
in — every later candidate of the tile ranks below it.  The trip count
is one more than the most candidates any row admits from the tile:
up to k+1 on the first tile, a handful once the list has warmed.  The
wrapper orders the final set with one small ``lax.sort`` on the same
total order.  That order does not depend on sweep order, so equal
scores resolve to the smallest item id, exactly like a top-k over the
materialised matrix — for the ascending sweep and for a permuted one.
Empty slots hold (−inf, 0), which no padding column (−inf, id ≥ 0)
can displace.

Dynamic pruning (the PQTopK follow-up, "Efficient Recommendation with
Millions of Items by Dynamic Pruning of Sub-Item Embeddings"):
``jpq_topk_tiles_pruned`` additionally takes a per-tile code-presence
mask and predicates the whole tile body (``pl.when``) on the score
upper bound ``ub = Σ_j max{P[j, c] : c in tile}`` beating the running
k-th value read from the revisited output block — most tiles of a
popularity-ordered catalogue are skipped exactly, with zero effect on
the result (an item's score never exceeds the bound, and an equal
score loses the id tie-break).  Two extras serve the mesh / warm-start
paths (docs/serving.md §pruning): ``floor [B]`` is a per-query
*candidate floor* — tiles whose bound falls strictly below it are also
skipped (admissible when the floor is ≤ the final k-th value: the
caller either derives it from real running scores via the cross-shard
exchange, or verifies it post hoc and demotes) — and ``init_vals`` /
``init_ids`` seed the running list at the first tile so a sweep can be
resumed across phases (the cross-shard threshold exchange splits one
sweep into two kernel launches).

VMEM per step (Bt=256, Nt=512, m=8, b=256, k=128):
  P tile   8·256·256·4 = 2.0 MiB     one-hot 256·512·2 = 0.25 MiB
  tile scores 256·512·4 = 0.5 MiB    running 2·256·128·4 = 0.25 MiB
-> ~3.5 MiB << 16 MiB.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.jpq_scores.jpq_scores import kernel_operands, tile_scores


def desc_sort_key(v):
    """int32 sort key: ascending key order == IEEE-total-order
    DESCENDING value order — i.e. exactly ``lax.top_k``'s ranking,
    including +0.0 above -0.0 (``lax.sort``'s float comparator ties
    ±0.0, top_k's does not, so float keys cannot reproduce top_k).
    Negation reverses the total order; the sign-magnitude -> ordered-int
    map is the classic radix-sort trick."""
    b = jax.lax.bitcast_convert_type(-v, jnp.int32)
    return jnp.where(b < 0, b ^ jnp.int32(0x7FFFFFFF), b)


def topk_total_order(cat_v, cat_i, k: int):
    """Exact top-k of candidates by (value desc, id asc) — the
    sweep-order-independent total order a permuted sweep needs, equal
    to stable ``lax.top_k`` over ascending-id candidates.

    Cost shape matters: a variadic 2-key ``lax.sort`` over the full
    candidate width W hits XLA CPU's scalar comparator loop, and int32
    ``top_k`` takes the same slow path (~30x slower than f32 top_k at
    W ~ 10^4).  So both wide reductions here are *f32* top_k passes —
    values directly (f32 top_k already ranks by the IEEE total order,
    +0.0 above -0.0), then negated ids masked to the k-th-value tie
    class (exact for ids < 2^24) — with bit-level int keys only in
    cheap elementwise compares, and the one variadic sort is over the
    assembled [B, 2k] pool:

      * the value pass fixes the output VALUE multiset
        (tie-independent) and the strictly-above-threshold ids;
      * the tie pass picks the (k - #strictly_above) smallest ids at
        the threshold value (bit-exact class: int key equality);
      * the small sort orders the union.
    """
    va, p1 = jax.lax.top_k(cat_v, k)
    ia = jnp.take_along_axis(cat_i, p1, axis=1)
    # the barrier stops XLA merging the θ slice into top_k's own
    # sort+slice lowering, which un-pattern-matches the CPU TopK
    # rewrite and silently degrades to a full W-wide sort (~25x)
    theta_v = jax.lax.optimization_barrier(va)[:, -1:]
    ikey = desc_sort_key(cat_v)                   # smaller = better
    tkey = desc_sort_key(theta_v)
    s = jnp.sum(ikey < tkey, axis=1)              # strictly above, <= k-1
    neg_ids = jnp.where(ikey == tkey, -cat_i.astype(jnp.float32),
                        -jnp.inf)
    ti = jax.lax.top_k(neg_ids, k)[0]             # smallest tie ids first
    cols = jax.lax.broadcasted_iota(jnp.int32, (1, k), 1)
    fill = cols < (k - s)[:, None]
    pool_v = jnp.concatenate(
        [jnp.where(desc_sort_key(va) < tkey, va, -jnp.inf),
         jnp.where(fill, jnp.broadcast_to(theta_v, ti.shape), -jnp.inf)],
        axis=1)
    pool_i = jnp.concatenate(
        [ia, jnp.where(fill, (-ti).astype(jnp.int32),
                       jnp.int32(2 ** 31 - 1))], axis=1)
    _, ii, vv = jax.lax.sort(
        (desc_sort_key(pool_v), pool_i, pool_v), num_keys=2)
    return vv[:, :k], ii[:, :k]


def sort_total_order(v, i):
    """Order (values, ids) rows by value desc, then id asc — the total
    order ``lax.top_k`` induces on the materialised matrix."""
    _, ii, vv = jax.lax.sort((desc_sort_key(v), i, v), num_keys=2)
    return vv, ii


_ID_MAX = 2 ** 31 - 1


def _any(mask):
    return jnp.max(mask.astype(jnp.int32)) > 0


def merge_tile(s, tile_ids, vals, ids):
    """Fold one tile into the running unordered top-k set (module
    docstring, "Merge").  s [Bt, Nt] fp32 tile scores, tile_ids
    [1 or Bt, Nt] int32 item ids, vals / ids [Bt, k] the set."""
    k = vals.shape[1]
    tile_ids = jnp.broadcast_to(tile_ids, s.shape)
    slot = jax.lax.broadcasted_iota(jnp.int32, vals.shape, 1)

    def body(carry):
        s, vals, ids, _ = carry
        bv = jnp.max(s, axis=1, keepdims=True)
        bi = jnp.min(jnp.where(s == bv, tile_ids, _ID_MAX), axis=1,
                     keepdims=True)
        wv = jnp.min(vals, axis=1, keepdims=True)
        wi = jnp.max(jnp.where(vals == wv, ids, -1), axis=1, keepdims=True)
        enter = (bv > wv) | ((bv == wv) & (bi < wi))
        ws = jnp.min(jnp.where((vals == wv) & (ids == wi), slot, k),
                     axis=1, keepdims=True)
        put = enter & (slot == ws)
        vals = jnp.where(put, bv, vals)
        ids = jnp.where(put, bi, ids)
        s = jnp.where(enter & (tile_ids == bi), -jnp.inf, s)
        return s, vals, ids, _any(enter)

    _, vals, ids, _ = jax.lax.while_loop(
        lambda c: c[3], body, (s, vals, ids, jnp.bool_(True)))
    return vals, ids


def _kernel(p_ref, codes_ref, vals_ref, ids_ref, *, block_n: int,
            n_items: int):
    # p_ref:     [m, Bt, b]  fp32 LUT tile (same block for every n step)
    # codes_ref: [m, Nt]     int32 codes tile
    # vals_ref:  [Bt, k]     running top-k set, values (revisited across n)
    # ids_ref:   [Bt, k]     running top-k set, item ids
    n = pl.program_id(1)

    @pl.when(n == 0)
    def _init():
        vals_ref[...] = jnp.full(vals_ref.shape, -jnp.inf, jnp.float32)
        ids_ref[...] = jnp.zeros(ids_ref.shape, jnp.int32)

    acc = tile_scores(p_ref, codes_ref)
    item_ids = n * block_n + jax.lax.broadcasted_iota(
        jnp.int32, (1, block_n), 1)
    acc = jnp.where(item_ids < n_items, acc, -jnp.inf)  # N-padding mask
    vals_ref[...], ids_ref[...] = merge_tile(acc, item_ids, vals_ref[...],
                                             ids_ref[...])


def _kernel_pruned(p_ref, codes_ref, ids_ref, pres_ref, floor_ref, iv_ref,
                   ii_ref, vals_ref, ids_out_ref, skip_ref, *,
                   block_n: int, n_items: int, n_batch: int,
                   tie_break_ids: bool):
    # p_ref:    [m, Bt, b]   fp32 LUT tile (same block for every n step)
    # codes_ref:[m, Nt]      int32 codes tile, in sweep order
    # ids_ref:  [1, Nt]      int32 ORIGINAL item id of each sweep row
    # pres_ref: [1, m, b]    fp32 0/1 — code c occurs in this tile, split j
    # floor_ref:[Bt, 1]      fp32 per-query candidate floor (-inf = none;
    #                        padded batch rows carry +inf so they never
    #                        demand a tile the real rows would skip)
    # iv_ref/ii_ref: [Bt, k] running-set seed written at n == 0 (-inf/0
    #                        for a cold sweep; the previous phase's lists
    #                        when resuming across a threshold exchange)
    # vals_ref / ids_out_ref: [Bt, k] running top-k set (revisited across n)
    # skip_ref: [1, N/Nt]    int32 SMEM row of this batch block's skip
    #                        flags: 1 iff tile n was skipped
    i = pl.program_id(0)
    n = pl.program_id(1)

    @pl.when(n == 0)
    def _init():
        vals_ref[...] = iv_ref[...]
        ids_out_ref[...] = ii_ref[...]

    # ---- score-bound: ub[t] = sum_j max{P[j, c] : c present in tile}.
    # Any item in the tile scores <= ub (its codes are all present), so
    # when ub cannot beat the running k-th value for ANY query row the
    # whole gather+accumulate+merge is provably a no-op and is skipped.
    m, bt, _ = p_ref.shape
    ub = jnp.zeros((bt, 1), jnp.float32)
    for j in range(m):
        pj = jnp.where(pres_ref[0, j:j + 1, :] > 0, p_ref[j], -jnp.inf)
        ub = ub + jnp.max(pj, axis=1, keepdims=True)
    # padded batch rows must never demand a tile
    row = i * bt + jax.lax.broadcasted_iota(jnp.int32, (bt, 1), 0)
    ub = jnp.where(row < n_batch, ub, -jnp.inf)
    theta = jnp.min(vals_ref[...], axis=1, keepdims=True)   # k-th value
    # identity sweep: an equal score loses the id tie-break to every
    # running entry (all from earlier tiles = smaller ids), so strict >
    # is required to enter.  Under a permutation ties break on original
    # id, so an equal-score smaller-id item CAN enter: keep >= tiles.
    ok = (ub >= theta) if tie_break_ids else (ub > theta)
    # the candidate floor is always strict-skip (ub == floor could tie
    # the final k-th value and win on id), and combines per ROW before
    # the any-reduce: a row whose bound clears its own θ but not the
    # floor must not demand the tile for everyone else.
    need = _any(ok & (ub >= floor_ref[...]))
    skip_ref[0, n] = jnp.where(need, 0, 1).astype(jnp.int32)

    @pl.when(need)
    def _body():
        acc = tile_scores(p_ref, codes_ref)
        # N-padding mask is by sweep POSITION (ids are original ids and
        # arbitrary under a permutation, positions are not)
        pos = n * block_n + jax.lax.broadcasted_iota(
            jnp.int32, (1, block_n), 1)
        acc = jnp.where(pos < n_items, acc, -jnp.inf)
        vals_ref[...], ids_out_ref[...] = merge_tile(
            acc, ids_ref[...], vals_ref[...], ids_out_ref[...])


@functools.partial(jax.jit, static_argnames=("k", "n_items", "n_batch",
                                             "block_b", "block_n",
                                             "tie_break_ids", "interpret"))
def jpq_topk_tiles_pruned(partial, codes, ids, present, floor, init_vals,
                          init_ids, *, k: int, n_items: int, n_batch: int,
                          block_b: int = 256, block_n: int = 512,
                          tie_break_ids: bool = False,
                          interpret: bool = False):
    """Score-bound dynamically-pruned variant of ``jpq_topk_tiles``.

    Extra inputs: ``ids [N]`` original item id per sweep row (iota
    when unpermuted), ``present [N/block_n, m, b]`` 0/1 presence of each
    code in each tile (built from the UNPADDED codes; padding rows
    contribute nothing, which only loosens nothing — they are masked by
    position), ``floor [B, 1]`` per-query candidate floor (-inf for a
    plain sweep; +inf on padded batch rows), ``init_vals`` /
    ``init_ids [B, k]`` the running-list seed (-inf / 0 cold, the prior
    phase's lists when resuming).  ``n_batch`` is the real (unpadded)
    batch size.  Returns (values [B, k], ids [B, k], skipped
    [B/Bt, N/Nt] int32 tile-skip map).  Bit-exact vs the materialise
    reference whenever every floor is ≤ the final k-th value (always
    true for -inf floors and exchange-derived floors; warm-start floors
    are verified and demoted by the caller)."""
    B, m, b = partial.shape
    N = codes.shape[0]
    assert B % block_b == 0 and N % block_n == 0, (B, N, block_b, block_n)
    # k may exceed n_items on a phased SUB-sweep (the running list keeps
    # its full width while a phase covers only a slice of the rows)
    assert 0 < k and 0 < n_items <= N, (k, n_items, N)
    grid = (B // block_b, N // block_n)
    assert present.shape == (grid[1], m, b), (present.shape, grid)
    assert floor.shape == (B, 1) and init_vals.shape == (B, k), \
        (floor.shape, init_vals.shape)
    v, i, skips = pl.pallas_call(
        functools.partial(_kernel_pruned, block_n=block_n,
                          n_items=n_items, n_batch=n_batch,
                          tie_break_ids=tie_break_ids),
        grid=grid,
        in_specs=[
            pl.BlockSpec((m, block_b, b), lambda i, n: (0, i, 0)),
            pl.BlockSpec((m, block_n), lambda i, n: (0, n)),
            pl.BlockSpec((1, block_n), lambda i, n: (0, n)),
            pl.BlockSpec((1, m, b), lambda i, n: (n, 0, 0)),
            pl.BlockSpec((block_b, 1), lambda i, n: (i, 0)),
            pl.BlockSpec((block_b, k), lambda i, n: (i, 0)),
            pl.BlockSpec((block_b, k), lambda i, n: (i, 0)),
        ],
        out_specs=(
            pl.BlockSpec((block_b, k), lambda i, n: (i, 0)),
            pl.BlockSpec((block_b, k), lambda i, n: (i, 0)),
            pl.BlockSpec((1, grid[1]), lambda i, n: (i, 0),
                         memory_space=pltpu.SMEM),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((B, k), jnp.float32),
            jax.ShapeDtypeStruct((B, k), jnp.int32),
            jax.ShapeDtypeStruct(grid, jnp.int32),
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="jpq_topk_pruned",
    )(*kernel_operands(partial, codes), ids.astype(jnp.int32)[None, :],
      present.astype(jnp.float32), floor.astype(jnp.float32),
      init_vals.astype(jnp.float32), init_ids.astype(jnp.int32))
    return (*sort_total_order(v, i), skips)


@functools.partial(jax.jit, static_argnames=("k", "n_items", "block_b",
                                             "block_n", "interpret"))
def jpq_topk_tiles(partial, codes, *, k: int, n_items: int,
                   block_b: int = 256, block_n: int = 512,
                   interpret: bool = False):
    """partial [B, m, b] fp32, codes [N, m] int (N padded to block_n,
    B padded to block_b by the caller) -> (values [B, k] fp32,
    ids [B, k] int32), top-k over the first ``n_items`` columns.
    Requires 0 < k <= n_items <= N."""
    B, m, b = partial.shape
    N = codes.shape[0]
    assert B % block_b == 0 and N % block_n == 0, (B, N, block_b, block_n)
    assert 0 < k <= n_items <= N, (k, n_items, N)
    grid = (B // block_b, N // block_n)
    v, i = pl.pallas_call(
        functools.partial(_kernel, block_n=block_n, n_items=n_items),
        grid=grid,
        in_specs=[
            pl.BlockSpec((m, block_b, b), lambda i, n: (0, i, 0)),
            pl.BlockSpec((m, block_n), lambda i, n: (0, n)),
        ],
        out_specs=(
            pl.BlockSpec((block_b, k), lambda i, n: (i, 0)),
            pl.BlockSpec((block_b, k), lambda i, n: (i, 0)),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((B, k), jnp.float32),
            jax.ShapeDtypeStruct((B, k), jnp.int32),
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="jpq_topk",
    )(*kernel_operands(partial, codes))
    return sort_total_order(v, i)

"""jit'd public wrappers for the fused PQTopK serving path.

Three backends behind one call:
  "pallas"    - the Mosaic kernel (TPU; the deploy target)
  "interpret" - the same kernel through the Pallas interpreter — the
                CPU parity oracle for tests
  "scan"      - a mathematically *identical* lax.scan over item blocks
                (gather tile scores, block-local top-k, one final merge
                over the [B, nb·k] candidates) — the fast CPU/GPU
                fallback.  Blocks sweep in ascending-id order and every
                top_k is stable, so values AND tie-broken ids match the
                kernel bit-for-bit at any block_n.  Peak live score
                buffer: [B, block_n] + [nb, B, k] candidates, never
                [B, N].

``backend=None`` resolves to "pallas" on TPU and "scan" elsewhere.
All entrypoints clamp ``k`` to ``min(k, N)`` (lax.top_k on the
materialised matrix would reject k > N) and handle N not a multiple of
block_n by masking padded columns to −inf against the real N.

Dynamic pruning (``prune=``): per-tile score upper bounds from the
query LUT skip tiles that provably cannot enter the top-k — see
``prepare_pruning`` and docs/serving.md.  ``prune=True`` builds the
(query-independent) presence mask inline; serving replicas should
build a ``PruneState`` ONCE via ``prepare_pruning`` and pass it, so
the per-request jit does none of that O(N·m) work.  Results are
bit-exact vs the unpruned path in every mode, permuted or not.

Warm start (``warm=``, pruned path only): a per-query (or scalar)
candidate floor — typically an EMA of past requests' final k-th
values (``core.serve.ThresholdState``) — lets the FIRST tiles of a
request prune before the running list has warmed.  The floor never
enters the list; it only strict-skips tiles whose bound falls below
it.  Admissibility is verified post hoc: if a query ends with fewer
than k scores ≥ its floor, the floor overshot the true k-th value and
the sweep is re-run with that query's floor demoted to -inf
(``lax.cond`` — one extra sweep only when the EMA overshoots), so the
result stays bit-exact unconditionally.

Signed zeros: both entrypoints canonicalise ``-0.0 → +0.0`` in the
LUT (numerically identical scores) — the one-hot MXU dot flattens the
sign while a gather keeps it, and ``lax.top_k``'s IEEE total order
splits ±0.0 ties — so every backend agrees bit-for-bit with the
materialise reference over the canonicalised LUT, ±0.0 ties included.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Union

import jax
import jax.numpy as jnp

from repro.kernels.jpq_scores.ops import _ceil_mult, _on_tpu
from repro.kernels.jpq_topk.jpq_topk import (desc_sort_key,  # noqa: F401
                                             jpq_topk_tiles,
                                             jpq_topk_tiles_pruned,
                                             topk_total_order)


class PruneState(NamedTuple):
    """Query-independent pruning inputs for one (codes, block_n) pair.

    codes   [N, m] int32   codebook rows in SWEEP order (permuted when a
                           popularity permutation is in play)
    ids     [N]    int32   original item id of each sweep row
    present [nt, m, b] f32 0/1 — code c occurs in tile t, split j
    block_n int            tile size ``present`` was built for
    tie_break_ids bool     sweep order != ascending id (permuted): merges
                           must tie-break on original id explicitly
    """
    codes: jnp.ndarray
    ids: jnp.ndarray
    present: jnp.ndarray
    block_n: int
    tie_break_ids: bool


def prepare_pruning(codes, b: int, block_n: int, perm=None) -> PruneState:
    """Build the per-tile code-presence mask (and optional sweep
    permutation) for score-bound pruning.  O(N·m) scatter, codes-only —
    compute once per (codes, block_n), NOT per query."""
    codes = jnp.asarray(codes).astype(jnp.int32)
    N, m = codes.shape
    if perm is None:
        ids = jnp.arange(N, dtype=jnp.int32)
        sweep = codes
    else:
        # permuted merges route tie ids through an f32 top_k
        # (topk_total_order) — exact only while ids fit in f32
        assert N < 2 ** 24, f"permuted pruning caps at 2^24 ids, N={N}"
        ids = jnp.asarray(perm).astype(jnp.int32)
        assert ids.shape == (N,), (ids.shape, N)
        sweep = jnp.take(codes, ids, axis=0)
    nt = -(-N // block_n)
    tile = (jnp.arange(N, dtype=jnp.int32) // block_n)[:, None]
    split = jnp.arange(m, dtype=jnp.int32)[None, :]
    present = jnp.zeros((nt, m, b), jnp.float32)
    present = present.at[jnp.broadcast_to(tile, (N, m)),
                         jnp.broadcast_to(split, (N, m)), sweep].set(1.0)
    return PruneState(sweep, ids, present, int(block_n), perm is not None)


def _resolve_prune(prune, perm, codes, b: int, block_n: int):
    """True/PruneState -> a PruneState matching ``block_n`` (rebuilding
    the presence mask if it was prepared for a different tile size).

    Rebuild re-tiles the presence mask over ``prune.codes`` — which are
    ALREADY in sweep order — and keeps the stored ids: passing
    ``prune.ids`` back through ``prepare_pruning``'s perm would permute
    a second time and serve scores under the wrong item ids."""
    if isinstance(prune, PruneState):
        if prune.block_n == block_n:
            return prune
        st = prepare_pruning(prune.codes, b, block_n)
        return PruneState(st.codes, prune.ids, st.present, block_n,
                          prune.tie_break_ids)
    return prepare_pruning(codes, b, block_n, perm=perm)


def canonicalise_lut(partial):
    """-0.0 -> +0.0, numerically a no-op (−0.0 == +0.0): pins the
    signed-zero tie order to the id tie-break in every backend (the
    one-hot MXU dot flattens the sign of zero anyway)."""
    return jnp.where(partial == 0.0, 0.0, partial)


def _as_floor(warm, B: int):
    """warm (None | scalar | [B]) -> per-query f32 floor [B] or None."""
    if warm is None:
        return None
    return jnp.broadcast_to(jnp.asarray(warm, jnp.float32), (B,))


def jpq_topk(h, centroids, codes, k: int, *, block_b: int = 256,
             block_n: int | None = None, backend: str | None = None,
             prune: Union[bool, PruneState, None] = None, perm=None,
             warm=None):
    """h [..., d], centroids [m, b, dk], codes [N, m] ->
    (values, ids) [..., min(k, N)] — top-k catalogue retrieval without
    materialising the [..., N] score matrix."""
    m, b, dk = centroids.shape
    lead = h.shape[:-1]
    B = 1
    for s in lead:
        B *= s
    h2 = h.reshape(B, m, dk).astype(jnp.float32)
    partial = jnp.einsum("bmk,mck->bmc", h2, centroids.astype(jnp.float32))
    v, i = jpq_topk_lut(partial, codes, k, block_b=block_b,
                        block_n=block_n, backend=backend, prune=prune,
                        perm=perm, warm=warm)
    return v.reshape(*lead, -1), i.reshape(*lead, -1)


def jpq_topk_lut(partial, codes, k: int, *, block_b: int = 256,
                 block_n: int | None = None, backend: str | None = None,
                 prune: Union[bool, PruneState, None] = None, perm=None,
                 warm=None, return_stats: bool = False):
    """partial [B, m, b] fp32, codes [N, m] -> (values, ids)
    [B, min(k, N)].  block_n=None picks the backend's native tile:
    VMEM-sized (512) for the kernel, a dispatch-amortising near-divisor
    of N around _SCAN_BLOCK_N (131072) for the XLA scan; pruned scans
    default to _PRUNE_BLOCK_N (8192) so the bound has tiles to skip.

    ``prune``: falsy = the PR 2 paths, True = build a PruneState inline,
    or a precomputed ``prepare_pruning(...)`` result.  ``perm``: optional
    [N] sweep permutation (original item id per sweep position; only
    meaningful with prune).  ``warm``: optional scalar or [B] candidate
    floor (pruned path only) — see the module docstring's warm-start /
    demotion contract.  ``return_stats=True`` appends a dict with
    ``skipped_tiles`` / ``total_tiles`` / ``skips`` (per-tile skip
    vector) / ``theta`` (final per-query k-th value — the quantity a
    ``ThresholdState`` EMAs) / ``demoted`` ([B] bool: the warm floor
    overshot that query and the sweep re-ran — the per-request
    warm-hit signal serving metrics count); jnp values, pruned paths
    only.
    """
    if backend is None:
        backend = "pallas" if _on_tpu() else "scan"
    B, m, b = partial.shape
    N = codes.shape[0]
    k = min(int(k), N)
    assert k > 0 and backend in ("pallas", "interpret", "scan"), (k, backend)
    partial = canonicalise_lut(partial.astype(jnp.float32))
    if not prune:
        assert not return_stats, "stats are a pruned-path feature"
        assert warm is None, "warm floors are a pruned-path feature"
        if backend == "scan":
            bn = block_n or scan_block_n(N)
            return _jpq_topk_scan(partial, codes.astype(jnp.int32), k=k,
                                  block_n=min(bn, _ceil_mult(N, 128)))
        bb = min(block_b, _ceil_mult(B, 8))
        bn = min(block_n or 512, _ceil_mult(N, 128))
        Bp, Np = _ceil_mult(B, bb), _ceil_mult(N, bn)
        partial = jnp.pad(partial, ((0, Bp - B), (0, 0), (0, 0)))
        codes_p = jnp.pad(codes.astype(jnp.int32), ((0, Np - N), (0, 0)))
        v, i = jpq_topk_tiles(partial, codes_p, k=k, n_items=N, block_b=bb,
                              block_n=bn, interpret=backend == "interpret")
        return v[:B], i[:B]

    # a prebuilt state's own tile size wins over the backend default
    # (an explicit block_n still forces a rebuild): a replica serving a
    # mesh-built state unsharded must not silently re-scatter the
    # O(N·m) presence mask inside the per-request jit
    if block_n is None and isinstance(prune, PruneState):
        block_n = prune.block_n
    if backend == "scan":
        bn = min(block_n or prune_block_n(N), _ceil_mult(N, 128))
    else:
        bn = min(block_n or 512, _ceil_mult(N, 128))
    st = _resolve_prune(prune, perm, codes, b, bn)
    floor = _as_floor(warm, B)

    def sweep(fl):
        return pruned_sweep(partial, st, k, block_n=bn, backend=backend,
                            block_b=block_b, floor=fl)

    if floor is None:
        v, i, skips = sweep(None)
        demoted = jnp.zeros((B,), bool)
    else:
        # demotion rule: a floor is only admissible when ≤ the true
        # k-th value; v1[:, -1] ≥ floor certifies exactly that (list
        # values are real scores, so v1[:, -1] ≤ the true k-th).
        v1, i1, s1 = sweep(floor)
        ok = v1[:, -1] >= floor
        demoted = ~ok
        v, i, skips = jax.lax.cond(
            jnp.all(ok), lambda c: c,
            lambda c: sweep(jnp.where(ok, floor, -jnp.inf)),
            (v1, i1, s1))
    if return_stats:
        return v, i, {"skipped_tiles": jnp.sum(skips),
                      "total_tiles": skips.size,
                      "skips": skips, "theta": v[:, -1],
                      "demoted": demoted}
    return v, i


def pruned_sweep(partial, st: PruneState, k: int, *, block_n: int,
                 backend: str, block_b: int = 256, floor=None,
                 carry=None):
    """One score-bound pruned sweep over ALL rows of ``st`` (callers
    slice the state for phased sweeps).  ``floor [B]`` is the per-query
    candidate floor (None = -inf), ``carry`` an optional (vals, ids)
    [B, k] running-list seed from a previous phase.  Returns
    (values [B, k], ids [B, k], skips [n_tiles] int32) — ``skips[t]``
    is 1 iff tile t issued no work (kernel backend: for every batch
    block).  ``k`` may exceed the slice's row count (phased sweeps keep
    the full-width list across phases; unfilled slots stay -inf/0).
    ``partial`` must already be canonicalised fp32."""
    B = partial.shape[0]
    N = st.codes.shape[0]
    k = int(k)
    if floor is None:
        floor = jnp.full((B,), -jnp.inf, jnp.float32)
    if carry is None:
        carry = (jnp.full((B, k), -jnp.inf, jnp.float32),
                 jnp.zeros((B, k), jnp.int32))
    if backend == "scan":
        return _jpq_topk_scan_pruned(
            partial, st.codes, st.ids, st.present, floor, carry[0],
            carry[1], k=k, block_n=block_n,
            tie_break_ids=st.tie_break_ids)
    bb = min(block_b, _ceil_mult(B, 8))
    Bp, Np = _ceil_mult(B, bb), _ceil_mult(N, block_n)
    partial_p = jnp.pad(partial, ((0, Bp - B), (0, 0), (0, 0)))
    codes_p = jnp.pad(st.codes, ((0, Np - N), (0, 0)))
    ids_p = jnp.pad(st.ids, (0, Np - N))
    floor_p = jnp.pad(floor[:, None], ((0, Bp - B), (0, 0)),
                      constant_values=jnp.inf)
    iv_p = jnp.pad(carry[0], ((0, Bp - B), (0, 0)),
                   constant_values=-jnp.inf)
    ii_p = jnp.pad(carry[1], ((0, Bp - B), (0, 0)))
    v, i, skips = jpq_topk_tiles_pruned(
        partial_p, codes_p, ids_p, st.present, floor_p, iv_p, ii_p,
        k=k, n_items=N, n_batch=B, block_b=bb, block_n=block_n,
        tie_break_ids=st.tie_break_ids,
        interpret=backend == "interpret")
    # per-tile skip flags: a tile counts skipped when every batch-grid
    # block skipped it (gb == 1 for B <= block_b, the serving shape)
    return v[:B], i[:B], jnp.min(skips, axis=0)


_SCAN_BLOCK_N = 131072
_PRUNE_BLOCK_N = 8192
_KERNEL_BLOCK_N = 512       # VMEM-sized tile of the Mosaic kernel
_LANES = 128                # Mosaic tiles a block's lane dim in 128s


def scan_block_n(N: int, target: int = _SCAN_BLOCK_N) -> int:
    """Near-divisor block size for the scan backend: the closest tile
    count to N/target, so the padded tail is < 128 items instead of a
    half-empty block of wasted gathers."""
    nb = max(1, round(N / target))
    return _ceil_mult(-(-N // nb), 128)


def prune_block_n(N: int, target: int | None = None) -> int:
    """Tile size of a pruning state.  On TPU it is the kernel's own
    tile (512), which the pruned kernel sweeps.  The scan backend
    wants ~8k tiles: bounds need granularity to bite (at the unpruned
    ~128k tile every one of the b codes occurs in every tile, the
    presence mask saturates and no tile can ever be skipped), and 8k
    keeps the per-tile merge cheap relative to the gather."""
    if target is None:
        if _on_tpu():
            return min(_KERNEL_BLOCK_N, _ceil_mult(N, _LANES))
        target = _PRUNE_BLOCK_N
    return scan_block_n(N, target)


def mesh_prune_block_n(N: int, shards: int,
                       target: int | None = None) -> int:
    """Pruned tile size for a ``shards``-way row-sharded catalogue: the
    divisor of the per-shard row count closest to ``target`` (the
    platform's ``prune_block_n`` tile by default; on TPU only multiples
    of 128, which the kernel's blocks need), so one GLOBAL
    permute-then-shard PruneState tiles every shard's rows exactly
    (``core.sharded.fused_topk_over_codes`` refuses states whose tiles
    straddle shard boundaries — rebuilding per request is the O(N·m)
    bug this replaces)."""
    assert N % shards == 0, (N, shards)
    local_n = N // shards
    tpu = _on_tpu()
    if target is None:
        target = _KERNEL_BLOCK_N if tpu else _PRUNE_BLOCK_N
    step = _LANES if tpu else 1
    best = local_n if local_n % step == 0 else None
    d = 1
    while d * d <= local_n:
        if local_n % d == 0:
            for c in (d, local_n // d):
                if c % step == 0 and (best is None or
                                      abs(c - target) < abs(best - target)):
                    best = c
        d += 1
    if best is None:
        raise ValueError(
            f"no tile of a multiple of {step} rows divides the "
            f"{local_n}-row shards of a {shards}-way mesh (N={N})")
    return best


@functools.partial(jax.jit, static_argnames=("k", "block_n"))
def _jpq_topk_scan(partial, codes, *, k: int, block_n: int):
    """Blockwise gather + block-local top-k, one final candidate merge;
    the kernel's algorithm as plain XLA.

    Block-local top-k never drops a global winner (each block keeps its
    k best, ties to the smallest id), and the final stable top_k over
    blocks stacked in ascending-id order reproduces the materialised
    tie-break exactly."""
    B, m, b = partial.shape
    N = codes.shape[0]
    Np = _ceil_mult(N, block_n)
    nb = Np // block_n
    kb = min(k, block_n)
    codes_p = jnp.pad(codes, ((0, Np - N), (0, 0)))
    blocks = codes_p.reshape(nb, block_n, m)
    starts = jnp.arange(nb, dtype=jnp.int32) * block_n

    def step(_, xs):
        cb, n0 = xs                                       # [Nt, m], scalar
        s = jnp.take(partial[:, 0, :], cb[:, 0], axis=1)  # [B, Nt]
        for j in range(1, m):
            s = s + jnp.take(partial[:, j, :], cb[:, j], axis=1)
        if Np != N:                     # mask only the block crossing N
            ids = n0 + jnp.arange(block_n, dtype=jnp.int32)
            s = jax.lax.cond(n0 + block_n > N,
                             lambda x: jnp.where(ids[None, :] < N, x,
                                                 -jnp.inf),
                             lambda x: x, s)
        v, pos = jax.lax.top_k(s, kb)
        return None, (v, pos + n0)

    _, (vs, is_) = jax.lax.scan(step, None, (blocks, starts))
    cat_v = jnp.swapaxes(vs, 0, 1).reshape(B, nb * kb)    # ascending-id
    cat_i = jnp.swapaxes(is_, 0, 1).reshape(B, nb * kb)
    v, pos = jax.lax.top_k(cat_v, k)
    return v, jnp.take_along_axis(cat_i, pos, axis=1)


@functools.partial(jax.jit, static_argnames=("k", "block_n",
                                             "tie_break_ids"))
def _jpq_topk_scan_pruned(partial, codes, ids, present, floor, vals0,
                          idx0, *, k: int, block_n: int,
                          tie_break_ids: bool):
    """Score-bound pruned sweep as plain XLA: a lax.scan carrying the
    running (values, ids) top-k, each block step ``cond``-guarded on the
    tile bound beating the running k-th value.

    Unlike ``_jpq_topk_scan`` there is no deferred merge — the carry IS
    the global top-k after every step, which is what makes a threshold
    exist to prune against.  Exactness: an item's score is bounded by
    ``Σ_j max{P[j, c] : c in its tile}``; a skipped tile therefore
    cannot contribute an entry (strictly-below threshold, or tied — and
    ties lose to the smaller-id entries already in the list when the
    sweep is ascending; under a permutation the merge tie-breaks on
    original id, so only strictly-below tiles are skipped).  ``floor``
    [B] is the strict-skip candidate floor (admissible iff ≤ the final
    k-th value — the caller's contract); ``vals0``/``idx0`` [B, k] seed
    the running list (phased sweeps).  Returns (v, i, skips [nb])."""
    B, m, b = partial.shape
    N = codes.shape[0]
    Np = _ceil_mult(N, block_n)
    nb = Np // block_n
    blocks = jnp.pad(codes, ((0, Np - N), (0, 0))).reshape(nb, block_n, m)
    id_blocks = jnp.pad(ids, (0, Np - N)).reshape(nb, block_n)
    starts = jnp.arange(nb, dtype=jnp.int32) * block_n

    def step(carry, xs):
        vals, idx = carry
        cb, ib, pres, n0 = xs            # [Nt, m], [Nt], [m, b], scalar
        theta = vals[:, -1]
        ub = jnp.zeros((B,), jnp.float32)
        for j in range(m):
            pj = jnp.where(pres[j][None, :] > 0, partial[:, j, :],
                           -jnp.inf)
            ub = ub + jnp.max(pj, axis=1)
        ok = (ub >= theta) if tie_break_ids else (ub > theta)
        # the floor is strict-skip per ROW before the any-reduce: a row
        # clearing its own θ but not its floor must not demand the tile
        need = jnp.any(ok & (ub >= floor))

        def do(args):
            vals, idx = args
            s = jnp.take(partial[:, 0, :], cb[:, 0], axis=1)  # [B, Nt]
            for j in range(1, m):
                s = s + jnp.take(partial[:, j, :], cb[:, j], axis=1)
            pos = n0 + jnp.arange(block_n, dtype=jnp.int32)
            s = jnp.where(pos[None, :] < N, s, -jnp.inf)
            cat_v = jnp.concatenate([vals, s], axis=1)
            cat_i = jnp.concatenate(
                [idx, jnp.broadcast_to(ib[None, :], s.shape)], axis=1)
            if tie_break_ids:
                # (value, id) total order without a wide variadic sort
                return topk_total_order(cat_v, cat_i, k)
            v, p = jax.lax.top_k(cat_v, k)
            return v, jnp.take_along_axis(cat_i, p, axis=1)

        vals, idx = jax.lax.cond(need, do, lambda a: a, (vals, idx))
        return (vals, idx), 1 - need.astype(jnp.int32)

    (v, i), skips = jax.lax.scan(
        step, (vals0, idx0), (blocks, id_blocks, present, starts))
    return v, i, skips

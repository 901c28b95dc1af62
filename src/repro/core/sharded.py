"""Explicitly-sharded embedding ops (shard_map) for the cases where
GSPMD's default gather partitioning moves activations instead of
staying row-local.

``pooled_lookup``: EmbeddingBag over a row-sharded table.  Each model
shard gathers its own rows (out-of-range ids hit a masked clip) and
pools locally, so the only cross-device traffic is the pooled
``[B, d]`` psum — not the ``[B, H, d]`` pre-pool tensor GSPMD would
all-gather.  §Perf two-tower iteration 1: 17.6 GB -> ~0.07 GB of
collective payload per step.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec

from repro.dist import rules as _rules


def pooled_lookup(table, ids, weights):
    """table [V, d] (rows shardable over 'model'), ids [B, H] int,
    weights [B, H] float -> pooled [B, d] = sum_h w * table[ids]."""
    mesh = _rules._CTX.mesh
    V, d = table.shape
    if (mesh is None or "model" not in mesh.shape
            or V % mesh.shape["model"] != 0):
        e = jnp.take(table, ids, axis=0)
        return jnp.sum(e * weights[..., None].astype(e.dtype), axis=1)

    shards = mesh.shape["model"]
    rows = V // shards
    spec_ids = _rules.resolve_axes(("batch", None), ids.shape, mesh)
    spec_out = _rules.resolve_axes(("batch", None), (ids.shape[0], d),
                                   mesh)

    def body(tab, ids_l, w_l):
        pid = jax.lax.axis_index("model")
        loc = ids_l - pid * rows
        ok = (loc >= 0) & (loc < rows)
        e = jnp.take(tab, jnp.clip(loc, 0, rows - 1), axis=0)  # [b, H, d]
        w = w_l * ok.astype(w_l.dtype)
        pooled = jnp.sum(e * w[..., None].astype(e.dtype), axis=1)
        return jax.lax.psum(pooled, "model")

    f = shard_map(
        body, mesh=mesh,
        in_specs=(PartitionSpec("model", None), spec_ids, spec_ids),
        out_specs=spec_out, check_vma=False)
    return f(table, ids, weights.astype(table.dtype))


def _merge_local_topk(v, i, local_n: int, k: int):
    """Merge per-shard top-k candidate lists into the global top-k.

    v, i [B, k_loc] shard-local (ids shard-relative) -> (values, ids)
    [B, k] global.  All-gathers only the [B, shards·k_loc] candidates;
    shards concatenate in ascending-row order and top_k is stable, so
    ties resolve to the smallest global item id — identical to a top-k
    over the unsharded scores."""
    i = i + jax.lax.axis_index("model") * local_n
    v_all = jax.lax.all_gather(v, "model", axis=1, tiled=True)
    i_all = jax.lax.all_gather(i, "model", axis=1, tiled=True)
    vv, pos = jax.lax.top_k(v_all, k)
    return vv, jnp.take_along_axis(i_all, pos, axis=1)


def _merge_pruned_topk(v, i, k: int):
    """Total-order merge for the permute-then-shard pruned path.

    Pruned per-shard lists already carry ORIGINAL item ids (each
    shard's slice of the global id-map), and under a popularity
    permutation the concatenated candidates are not in ascending-id
    order — so the stable-top_k trick of ``_merge_local_topk`` cannot
    reproduce the materialised tie-break.  ``topk_total_order`` ranks
    the gathered [B, shards·k_loc] pool by (value desc, id asc) — the
    sweep-order-independent total order ``lax.top_k`` induces on the
    unsharded matrix — so the merge stays bit-exact, ties included.
    Exact while ids < 2^24 (the tie pass rides an f32 top_k)."""
    from repro.kernels.jpq_topk.jpq_topk import topk_total_order
    v_all = jax.lax.all_gather(v, "model", axis=1, tiled=True)
    i_all = jax.lax.all_gather(i, "model", axis=1, tiled=True)
    return topk_total_order(v_all, i_all, k)


def topk_over_items(scores, k: int):
    """Hierarchical top-k over an item-sharded score matrix.

    scores [B, N] (N shardable over 'model') -> (values, ids)
    [B, min(k, N)].  Local top-k per shard, all-gather only
    [B, shards*k] candidates, final top-k — instead of GSPMD gathering
    the full [B, N] matrix.  §Perf retrieval iteration.
    """
    mesh = _rules._CTX.mesh
    B, N = scores.shape
    k = min(int(k), N)
    if mesh is None or "model" not in mesh.shape \
            or N % mesh.shape["model"] != 0:
        return jax.lax.top_k(scores, k)
    local_n = N // mesh.shape["model"]
    k_loc = min(k, local_n)
    spec_b = _rules.resolve_axes(("batch", None), (B, N), mesh)
    out_spec = _rules.resolve_axes(("batch", None), (B, k), mesh)

    def body(s):                                   # [b, N/shards]
        return _merge_local_topk(*jax.lax.top_k(s, k_loc), local_n, k)

    f = shard_map(body, mesh=mesh,
                  in_specs=(PartitionSpec(spec_b[0], "model"),),
                  out_specs=(out_spec, out_spec), check_vma=False)
    return f(scores)


def fused_topk_over_codes(partial, codes, k: int, *, block_n: int | None = None,
                          backend: str | None = None, prune=None, perm=None,
                          warm=None, exchange_tiles: int | None = None,
                          return_stats: bool = False):
    """PQTopK serving: fused score+top-k over row-sharded codes.

    partial [B, m, b] fp32 LUT (replicated over 'model'), codes [N, m]
    (rows shardable over 'model') -> (values, ids) [B, min(k, N)].

    Each model shard runs the fused kernel over its own code rows —
    the [B, N] score matrix is never materialised, locally or
    globally — and only the [B, shards·k] candidate lists are
    all-gathered before the final merge.  Unpruned, shards sweep in
    ascending-row order and the stable merge ties-breaks on item id,
    bit-identical to the unsharded fused path (and to lax.top_k over
    materialised scores).  §Serve-path.

    Pruned serving is mesh-native (docs/serving.md §pruning):

    * **Permute-then-shard.**  ``prune`` may be a GLOBAL
      ``prepare_pruning(codes, b, mesh_prune_block_n(N, shards),
      perm=perm)`` state: the popularity permutation is applied to the
      catalogue rows BEFORE the row-shard split, so each shard sweeps
      its own rows in descending-popularity order (its slice of the
      permuted codes + id-map), and the merge converts nothing — local
      lists already carry original ids and are total-order merged
      (``_merge_pruned_topk``), bit-exact ties included.  The state is
      built once per catalogue and row-sliced by shard_map every
      request; a state whose tiles straddle shard boundaries raises
      (silently rebuilding per request was the O(N·m) bug).
      ``prune=True`` builds the global state inline (tests/one-offs).
    * **Cross-shard threshold exchange.**  After each shard's first
      ``exchange_tiles`` tiles, the running k_loc-th values are
      max-reduced across shards (one [B]-scalar collective) and the
      rest of the sweep also prunes against that global floor —
      admissible because the exchanged value is the k-th of a real
      score subset (≤ the final global k-th), and strictly tighter
      than per-shard-only thresholds.  Strict-skip only: an equal
      bound could tie the global k-th and win on id.
    * **Warm start.**  ``warm`` (scalar or [B]) floors the sweep from
      tile 0; admissibility is verified on the MERGED k-th value and
      inadmissible queries are demoted and re-swept (lax.cond), so
      results stay bit-exact unconditionally.

    ``return_stats=True`` appends {"skipped_tiles", "total_tiles",
    "skips", "theta", "exchange_tiles", "demoted"} (``demoted`` [B]
    bool — the warm floor overshot that query and it was re-swept; the
    per-request warm-hit signal serving metrics count): tile counts are
    aggregated
    across model shards and averaged over data shards (mean weighted
    by local tile count — every shard sweeps the same tile count).
    """
    from repro.kernels.jpq_topk import ops as _tops
    mesh = _rules._CTX.mesh
    B = partial.shape[0]
    N = codes.shape[0]
    k_out = min(int(k), N)
    if not prune and (warm is not None or return_stats):
        raise ValueError(
            "warm floors / stats are pruned-path features: the warm "
            "floor seeds the pruning threshold and the stats dict "
            "counts skipped tiles, neither of which exists on the "
            "unpruned sweep — pass prune=True (or a prepare_pruning(...) "
            "state), or drop warm=/return_stats=")
    if (mesh is None or "model" not in mesh.shape
            or N % mesh.shape["model"] != 0):
        return _tops.jpq_topk_lut(partial, codes, k_out, block_n=block_n,
                                  backend=backend, prune=prune, perm=perm,
                                  warm=warm, return_stats=return_stats)
    shards = mesh.shape["model"]
    local_n = N // shards
    k_loc = min(k_out, local_n)
    spec_b = _rules.resolve_axes(("batch", None), (B, N), mesh)
    out_spec = _rules.resolve_axes(("batch", None), (B, k_out), mesh)

    if not prune:
        def body(part_l, codes_l):           # [b, m, b_c], [N/shards, m]
            v, i = _tops.jpq_topk_lut(part_l, codes_l, k_loc,
                                      block_n=block_n, backend=backend)
            return _merge_local_topk(v, i, local_n, k_out)

        f = shard_map(
            body, mesh=mesh,
            in_specs=(PartitionSpec(spec_b[0], None, None),
                      PartitionSpec("model", None)),
            out_specs=(out_spec, out_spec), check_vma=False)
        return f(partial, codes)

    # ---------------------------------------- mesh-native pruned path
    assert N < 2 ** 24, \
        f"total-order merge routes ids through f32 top_k; N={N}"
    b_cent = partial.shape[2]
    if isinstance(prune, _tops.PruneState):
        st = prune
        if st.codes.shape[0] != N:
            raise ValueError(f"PruneState covers {st.codes.shape[0]} rows, "
                             f"catalogue has {N}")
        if local_n % st.block_n != 0:
            raise ValueError(
                f"PruneState block_n={st.block_n} straddles the "
                f"{local_n}-row shards of a {shards}-way mesh; build it "
                f"once with prepare_pruning(codes, b, "
                f"mesh_prune_block_n(N, shards), perm=perm)")
        bn = st.block_n
    else:
        bn = block_n if (block_n and local_n % block_n == 0) \
            else _tops.mesh_prune_block_n(N, shards)
        st = _tops.prepare_pruning(codes, b_cent, bn, perm=perm)
    backend_r = backend or ("scan" if not _tops._on_tpu() else "pallas")
    nt_loc = local_n // bn
    # one exchange point: as soon as every shard's running list holds
    # k_loc REAL candidates — ceil(k/bn) tiles, usually ONE — the pmax
    # is already the max over shards of a full k-th value (for the
    # popular shard that is ≈ the final θ under a popularity sweep),
    # and every pre-exchange tile is one the tail shards sweep against
    # their own loose local thresholds.  Only meaningful when the
    # exchanged k_loc-th value bounds the global k-th (k_loc == k_out)
    # and there is more than one shard and tile.
    t_ex = None
    if shards > 1 and nt_loc > 1 and k_loc == k_out:
        t_ex = exchange_tiles if exchange_tiles else -(-k_loc // bn)
        t_ex = min(int(t_ex), nt_loc - 1)
    data_degree = 1
    for ax, sz in mesh.shape.items():
        if ax != "model":
            data_degree *= sz
    all_axes = tuple(mesh.shape)
    partial = _tops.canonicalise_lut(partial.astype(jnp.float32))
    floor0 = jnp.full((B,), -jnp.inf, jnp.float32) if warm is None \
        else jnp.broadcast_to(jnp.asarray(warm, jnp.float32), (B,))

    def body(part_l, codes_l, ids_l, pres_l, fl):
        def sub(lo, hi):                     # tile-range slice of state
            return _tops.PruneState(codes_l[lo * bn:hi * bn],
                                    ids_l[lo * bn:hi * bn],
                                    pres_l[lo:hi], bn, st.tie_break_ids)

        if t_ex is not None:
            v1, i1, s1 = _tops.pruned_sweep(
                part_l, sub(0, t_ex), k_loc, block_n=bn,
                backend=backend_r, floor=fl)
            # running k_loc-th values are real scores: their cross-shard
            # max is ≤ the final global k-th, hence an admissible floor
            theta_ex = jax.lax.pmax(v1[:, -1], "model")
            v2, i2, s2 = _tops.pruned_sweep(
                part_l, sub(t_ex, nt_loc), k_loc, block_n=bn,
                backend=backend_r, floor=jnp.maximum(fl, theta_ex),
                carry=(v1, i1))
            skips = jnp.concatenate([s1, s2])
        else:
            v2, i2, skips = _tops.pruned_sweep(
                part_l, sub(0, nt_loc), k_loc, block_n=bn,
                backend=backend_r, floor=fl)
        vm, im = _merge_pruned_topk(v2, i2, k_out)
        if not return_stats:
            return vm, im
        # model shards sweep disjoint tiles (sum); data shards repeat
        # the sweep for their batch slice (mean — psum then /degree,
        # which also collapses the replicated case exactly)
        sk = jax.lax.psum(jnp.sum(skips).astype(jnp.float32),
                          all_axes) / data_degree
        skv = jax.lax.psum(
            skips.astype(jnp.float32),
            tuple(a for a in all_axes if a != "model")) / data_degree
        return vm, im, sk, skv

    stat_specs = (PartitionSpec(), PartitionSpec("model"))
    f = shard_map(
        body, mesh=mesh,
        in_specs=(PartitionSpec(spec_b[0], None, None),
                  PartitionSpec("model", None), PartitionSpec("model"),
                  PartitionSpec("model", None, None),
                  PartitionSpec(spec_b[0])),
        out_specs=(out_spec, out_spec) + (stat_specs if return_stats
                                          else ()),
        check_vma=False)

    def run(fl):
        return f(partial, st.codes, st.ids, st.present, fl)

    if warm is None:
        out = run(floor0)
        demoted = jnp.zeros((B,), bool)
    else:
        out1 = run(floor0)
        # warm demotion: the merged k-th value certifies the floor
        # (list values are real scores ≤ the true global k-th)
        ok = out1[0][:, -1] >= floor0
        demoted = ~ok
        out = jax.lax.cond(
            jnp.all(ok), lambda o: o,
            lambda o: run(jnp.where(ok, floor0, -jnp.inf)), out1)
    if not return_stats:
        return out
    vm, im, sk, skv = out
    stats = {"skipped_tiles": sk, "total_tiles": nt_loc * shards,
             "skips": skv, "theta": vm[:, -1],
             "exchange_tiles": 0 if t_ex is None else t_ex,
             "demoted": demoted}
    return vm, im, stats

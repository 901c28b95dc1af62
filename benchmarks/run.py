"""Benchmark harness — one function per paper table/figure + the
serving hot-path microbench and the dry-run roofline reader.

  table2_memory     : paper Table 2  (PQ memory analysis per dataset)
  table45_strategies: paper Tables 4/5 (strategy × backbone NDCG + size,
                      reduced scale; full run = examples/paper_validation)
  fig3_grid         : paper Fig. 3  (code length m × embedding size d)
  fig4_tradeoff     : paper Fig. 4  (model size vs NDCG, base vs RecJPQ)
  jpq_scoring       : serving hot path — full-table vs JPQ-partial-score
                      vs Pallas kernel (interpret), us/call + bytes moved
  jpq_topk          : PQTopK fused score+top-k vs materialise-then-top-k
                      at N ∈ {100k, 1M} (full mode), time + peak bytes
  serve_latency     : request-level continuous-batching server under
                      open-loop Poisson load — end-to-end p50/p99 per
                      request (queueing included) for sync-loop vs
                      micro-batched vs warm-merged replica configs
  kernels           : Pallas kernel suite (jpq_scores / jpq_lookup /
                      embedding_bag) in interpret mode vs refs — CPU
                      wall + max|Δ| parity column (TPU tiles are the
                      production target; interpret is the CI oracle)
  grad_exchange     : elastic compressed-gradient exchange — per-method
                      payload bytes / exchange fraction (the numbers
                      the Trainer emits per step and dist.hlo
                      cross-checks in HLO) + single-host step wall
  roofline          : aggregates experiments/dryrun JSONs (§Roofline)

Output: ``name,us_per_call,derived`` CSV rows (derived = the metric the
paper's table reports).  ``--json`` emits the same rows as one JSON
array (what tests/test_benchmarks.py parses); ``--smoke`` shrinks every
subcommand to seconds for that smoke test.  Default is fast mode;
``--full`` runs the paper-scale versions.
"""
from __future__ import annotations

import argparse
import glob
import json
import os
import sys

# the jpq_topk mesh rows shard the catalogue over 8 host devices; the
# flag must land before jax initialises.  Unsharded benches still run
# on device 0, but splitting the host does shift absolute CPU walls a
# little — every number quoted in docs/EXPERIMENTS was (re)measured
# under this flag, so compare like with like
if "xla_force_host_platform_device_count" not in \
        os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from benchmarks.common import time_fn, train_seqrec  # noqa: E402
from repro.core import EmbeddingConfig, build_codebook  # noqa: E402
from repro.core.api import compression_report  # noqa: E402


_SMOKE = False          # --smoke: shrink every bench to seconds
_JSON = False           # --json: one JSON array instead of CSV rows
_ROWS = []


def _row(name, us, derived):
    _ROWS.append({"name": name,
                  "us_per_call": None if us is None else float(us),
                  "derived": str(derived)})
    if not _JSON:
        print(f"{name},{us if us is not None else ''},{derived}",
              flush=True)


# ----------------------------------------------------------- Table 2

def table2_memory():
    """PQ impact on embedding-tensor memory (d=512 fp32, like the paper)."""
    datasets = [("MovieLens-1M", 3416), ("Booking.com", 34742),
                ("Gowalla", 1_280_969)]
    for name, n in datasets:
        base = n * 512 * 4
        for m in (2, 8, 32):
            rep = compression_report(EmbeddingConfig(
                n_items=n, d=512, kind="jpq", m=m, b=256))
            _row(f"table2/{name}/m={m}", None,
                 f"{rep['pct_of_base']:.3f}%_of_{base/1e6:.2f}MB")


# -------------------------------------------------------- Tables 4/5

def _make_data(profile: str, fast: bool):
    from repro.data.sequences import SeqDataConfig, SyntheticSequences
    if profile == "ml1m":      # dense, no long tail
        cfg = SeqDataConfig(n_users=300 if fast else 800, n_items=240,
                            zipf_a=0.3, min_len=12, max_len=60,
                            seq_len=32, seed=0)
    else:                      # gowalla-like long tail
        cfg = SeqDataConfig(n_users=400 if fast else 1200, n_items=2000,
                            zipf_a=1.3, min_len=6, max_len=30,
                            seq_len=24, seed=1)
    if _SMOKE:
        import dataclasses
        cfg = dataclasses.replace(cfg, n_users=120, n_items=80,
                                  seq_len=12, min_len=6, max_len=12)
    return SyntheticSequences(cfg)


def _variant_model(arch, data, variant, d_model=64, m=8, b=64):
    from repro.models.sequential import SeqRecConfig, SeqRecModel
    n_items = data.cfg.n_items
    codes = None
    if variant.startswith("jpq"):
        strat = variant.split("-")[1]
        u, i = data.train_interactions()
        codes = build_codebook(strat, n_items + 2, m, b,
                               interactions=(u, i + 1),
                               n_users=data.n_users_eff, seed=0,
                               **({"epochs": 3} if strat == "bpr" else {}))
        emb = EmbeddingConfig(0, 0, kind="jpq", m=m, b=b)
    elif variant == "qr":
        emb = EmbeddingConfig(0, 0, kind="qr")
    else:
        emb = None
    cfg = SeqRecConfig(arch=arch, n_items=n_items, max_len=data.cfg.seq_len,
                       d_model=d_model, n_layers=2, n_heads=2, d_ff=128,
                       embedding=emb)
    return SeqRecModel(cfg, codes=codes)


def table45_strategies(fast: bool = True):
    """Reduced-scale Tables 4/5: NDCG@10 + relative model size."""
    steps = 2 if _SMOKE else (150 if fast else 600)
    archs = ["sasrec"] if fast else ["sasrec", "gru4rec"]
    for profile in (["gowalla"] if fast else ["ml1m", "gowalla"]):
        data = _make_data(profile, fast)
        for arch in archs:
            base_bytes = None
            for variant in ["base", "qr", "jpq-random", "jpq-svd",
                            "jpq-bpr"]:
                model = _variant_model(arch, data, variant)
                _, ndcg, nbytes = train_seqrec(model, data, steps=steps)
                if variant == "base":
                    base_bytes = nbytes
                rel = 100.0 * nbytes / base_bytes
                _row(f"table45/{profile}/{arch}/{variant}", None,
                     f"ndcg10={ndcg:.4f};rel_size={rel:.1f}%")


# ------------------------------------------------------------ Fig. 3

def fig3_grid(fast: bool = True):
    data = _make_data("gowalla", fast=True)
    steps = 2 if _SMOKE else (120 if fast else 400)
    ds = [32] if _SMOKE else ([32, 64] if fast else [16, 32, 64, 128])
    ms = [2] if _SMOKE else ([2, 8] if fast else [1, 2, 4, 8, 16])
    for d in ds:
        for m in ms:
            if m > d:
                continue
            model = _variant_model("sasrec", data, "jpq-svd", d_model=d,
                                   m=m)
            _, ndcg, _ = train_seqrec(model, data, steps=steps)
            _row(f"fig3/d={d}/m={m}", None, f"ndcg10={ndcg:.4f}")


# ------------------------------------------------------------ Fig. 4

def fig4_tradeoff(fast: bool = True):
    data = _make_data("gowalla", fast=True)
    steps = 2 if _SMOKE else (120 if fast else 400)
    for d in ([32] if _SMOKE else
              [32, 64] if fast else [16, 32, 64, 128, 256]):
        for variant in ("base", "jpq-svd"):
            model = _variant_model("sasrec", data, variant, d_model=d)
            _, ndcg, nbytes = train_seqrec(model, data, steps=steps)
            _row(f"fig4/{variant}/d={d}", None,
                 f"ndcg10={ndcg:.4f};bytes={nbytes}")


# ----------------------------------------------- serving microbench

def jpq_scoring(fast: bool = True):
    """The paper's trick as a serving bandwidth win (CPU wall-clock is a
    proxy; the structural win is the bytes column)."""
    from repro.core import jpq as jpq_mod
    from repro.core import full as full_mod
    from repro.kernels.jpq_scores.ops import jpq_scores
    from repro.nn.module import KeyGen

    N, d, m, b, B = (100_000 if fast else 1_000_000), 256, 8, 256, 16
    if _SMOKE:
        N = 20_000
    pf = full_mod.init(KeyGen(0), N, d)
    pj = jpq_mod.init(KeyGen(1), N, d, m, b)
    h = jax.random.normal(jax.random.PRNGKey(2), (B, d))

    f_full = jax.jit(lambda hh: full_mod.logits(pf, hh))
    f_jpq = jax.jit(lambda hh: jpq_mod.logits(pj, hh))
    us_full = time_fn(f_full, h, iters=10)
    us_jpq = time_fn(f_jpq, h, iters=10)
    _row("jpq_scoring/full_table", f"{us_full:.0f}",
         f"bytes_read={N * d * 4}")
    _row("jpq_scoring/jpq_partial", f"{us_jpq:.0f}",
         f"bytes_read={N * m + b * d * 4}")
    if not fast:
        f_kern = jax.jit(lambda hh: jpq_scores(
            hh, pj["centroids"].value, pj["codes"].value))
        us_k = time_fn(f_kern, h, iters=5)
        _row("jpq_scoring/pallas_interpret", f"{us_k:.0f}",
             "interpret-mode (TPU target)")

    # embedding-bag hot path
    from repro.kernels.embedding_bag.ref import embedding_bag_ref
    V, dd, nb, L = (5_000, 64, 256, 16) if _SMOKE else \
        (50_000, 64, 4096, 16)
    tab = jax.random.normal(jax.random.PRNGKey(3), (V, dd))
    ids = jax.random.randint(jax.random.PRNGKey(4), (nb, L), 0, V)
    w = jnp.ones((nb, L))
    f_bag = jax.jit(lambda t, i, ww: embedding_bag_ref(t, i, ww))
    _row("embedding_bag/gather_segsum", f"{time_fn(f_bag, tab, ids, w):.0f}",
         f"nnz={nb * L}")


# --------------------------------------------- fused serving top-k

def jpq_topk_bench(fast: bool = True):
    """PQTopK fused score+top-k vs materialise-then-top-k (the serve
    path `retrieve_topk` replaced), plus the score-bound dynamically
    pruned sweep.  Peak score buffer: [B, block_n] + [nb, B, k]
    candidates instead of [B, N].  CPU wall-clock; the structural win
    (and the Pallas kernel) targets TPU HBM traffic.

    The pruned rows run a popularity-structured catalogue (codes
    correlate with popularity rank, the sweep is popularity-permuted —
    what `core.assign.{build_codebook,popularity_permutation}` produce
    on real interaction data): the threshold tightens within the first
    tiles and the long tail is skipped.  On uniform-random codes every
    tile contains every code, bounds saturate, and pruning is a no-op
    by construction — that instance stays as the unpruned baseline."""
    import functools
    from repro.kernels.jpq_topk import ops as tops
    from repro.kernels.jpq_topk.ref import jpq_topk_lut_ref

    B, m, b, k = (8, 8, 256, 100) if _SMOKE else (64, 8, 256, 100)
    key = jax.random.PRNGKey(0)
    partial = jax.random.normal(key, (B, m, b))
    for N in ([20_000] if _SMOKE else
              [100_000] if fast else [100_000, 1_000_000]):
        bn = tops.scan_block_n(N)
        codes = jax.random.randint(jax.random.fold_in(key, N), (N, m),
                                   0, b, jnp.int32).astype(jnp.uint8)
        f_ref = jax.jit(functools.partial(jpq_topk_lut_ref, k=k))
        f_fus = jax.jit(functools.partial(tops.jpq_topk_lut, k=k,
                                          backend="scan"))
        us_ref = time_fn(f_ref, partial, codes, iters=5, warmup=1)
        us_fus = time_fn(f_fus, partial, codes, iters=5, warmup=1)
        rv, ri = f_ref(partial, codes)
        fv, fi = f_fus(partial, codes)
        exact = bool(np.array_equal(np.asarray(rv), np.asarray(fv))
                     and np.array_equal(np.asarray(ri), np.asarray(fi)))
        _row(f"jpq_topk/N={N}/materialise", f"{us_ref:.0f}",
             f"peak_scores_bytes={B * N * 4}")
        _row(f"jpq_topk/N={N}/fused", f"{us_fus:.0f}",
             f"peak_scores_bytes={B * bn * 4};"
             f"speedup={us_ref / us_fus:.2f}x;exact_match={exact}")

        # ---- pruned sweep on the popularity-structured instance
        kp = jax.random.fold_in(key, N + 1)
        rank = jax.random.permutation(jax.random.fold_in(kp, 1),
                                      N).astype(jnp.int32)  # pop rank/item
        jitter = jax.random.randint(jax.random.fold_in(kp, 2), (N, m),
                                    0, max(b // 16, 1))
        codes_p = jnp.clip((rank[:, None].astype(jnp.int32) * b) // N
                           + jitter, 0, b - 1).astype(jnp.uint8)
        lut = (-(jnp.arange(b) / b)[None, None, :] * 4.0
               + 0.1 * jax.random.normal(jax.random.fold_in(kp, 3),
                                         (B, m, b))).astype(jnp.float32)
        perm = jnp.argsort(rank).astype(jnp.int32)    # sweep: popular 1st
        pbn = tops.prune_block_n(N)
        state = tops.prepare_pruning(codes_p, b, pbn, perm=perm)
        jax.block_until_ready(state)      # codes-only; built ONCE, like
        #                                   a serving replica would
        f_base = jax.jit(functools.partial(tops.jpq_topk_lut, k=k,
                                           backend="scan"))
        f_prn = jax.jit(functools.partial(tops.jpq_topk_lut, k=k,
                                          backend="scan", prune=state))
        us_base = time_fn(f_base, lut, codes_p, iters=5, warmup=1)
        us_prn = time_fn(f_prn, lut, codes_p, iters=5, warmup=1)
        rv, ri = jax.jit(functools.partial(jpq_topk_lut_ref, k=k))(
            lut, codes_p)
        pv, pi, stats = tops.jpq_topk_lut(lut, codes_p, k,
                                          backend="scan", prune=state,
                                          return_stats=True)
        exact = bool(np.array_equal(np.asarray(rv), np.asarray(pv))
                     and np.array_equal(np.asarray(ri), np.asarray(pi)))
        frac = float(stats["skipped_tiles"]) / float(stats["total_tiles"])
        _row(f"jpq_topk/N={N}/fused_popular", f"{us_base:.0f}",
             "unpruned sweep, popularity-structured codes")
        _row(f"jpq_topk/N={N}/pruned", f"{us_prn:.0f}",
             f"skipped_tile_frac={frac:.3f};"
             f"speedup_vs_fused={us_base / us_prn:.2f}x;"
             f"exact_match={exact}")

        # ---- mesh-native pruned serving: permute-then-shard + cross-
        # shard threshold exchange (+ EMA warm start) on an 8-way
        # model mesh — skip fraction aggregated across shards must
        # track the unsharded permuted sweep (docs/serving.md)
        from repro import dist
        from repro.core import sharded
        from repro.launch.mesh import make_mesh
        shards = 8
        if N % shards or jax.device_count() < shards:
            # a caller-preset XLA_FLAGS can pin fewer host devices;
            # skip the mesh rows rather than abort the whole bench
            continue
        mesh = make_mesh((1, shards), ("data", "model"))
        local_n = N // shards
        bn_m = tops.mesh_prune_block_n(
            N, shards, target=min(8192, max(128, local_n // 8)))
        state_m = tops.prepare_pruning(codes_p, b, bn_m, perm=perm)
        jax.block_until_ready(state_m)    # built ONCE per catalogue
        nt_loc = local_n // bn_m
        with dist.use_mesh_rules(mesh):
            f_mesh = jax.jit(lambda l, c: sharded.fused_topk_over_codes(
                l, c, k, prune=state_m, return_stats=True))
            f_warm = jax.jit(
                lambda l, c, w: sharded.fused_topk_over_codes(
                    l, c, k, prune=state_m, warm=w, return_stats=True))
            mv, mi, mstats = jax.block_until_ready(
                f_mesh(lut, codes_p))
            us_mesh = time_fn(f_mesh, lut, codes_p, iters=5, warmup=0)
            warm_vec = mstats["theta"]    # EMA seed: previous request θ
            wv, wi, wstats = jax.block_until_ready(
                f_warm(lut, codes_p, warm_vec))
            us_warm = time_fn(f_warm, lut, codes_p, warm_vec, iters=5,
                              warmup=0)
        m_exact = bool(np.array_equal(np.asarray(rv), np.asarray(mv))
                       and np.array_equal(np.asarray(ri), np.asarray(mi)))
        w_exact = bool(np.array_equal(np.asarray(rv), np.asarray(wv))
                       and np.array_equal(np.asarray(ri), np.asarray(wi)))
        m_frac = float(mstats["skipped_tiles"]) / float(
            mstats["total_tiles"])
        w_frac = float(wstats["skipped_tiles"]) / float(
            wstats["total_tiles"])
        t_ex = int(np.asarray(wstats["exchange_tiles"]))
        first = max(t_ex, 1)              # pre-exchange window
        skv = np.asarray(wstats["skips"]).reshape(shards, nt_loc)
        w_first = float(skv[:, :first].sum())
        _row(f"jpq_topk/N={N}/mesh8_pruned", f"{us_mesh:.0f}",
             f"skipped_tile_frac={m_frac:.3f};"
             f"delta_vs_unsharded={m_frac - frac:+.3f};"
             f"exact_match={m_exact}")
        _row(f"jpq_topk/N={N}/mesh8_warm", f"{us_warm:.0f}",
             f"skipped_tile_frac={w_frac:.3f};"
             f"first_window_skips={w_first:.0f}/{shards * first};"
             f"exact_match={w_exact}")


# ------------------------------------------- request-level serving

def serve_latency(fast: bool = True):
    """End-to-end REQUEST latency under open-loop Poisson load through
    the continuous-batching server (repro.serve) — the number the
    batch-latency loop (launch/serve.py) cannot see, because it
    includes the time a request spends waiting to be coalesced.

    Three configs over the same arrival stream: ``sync-loop``
    (max_batch=1 — every request dispatched alone, no queueing but no
    batching), ``queue`` (micro-batched under the latency budget), and
    ``queue+warm-merged`` (two replicas with periodically merged warm
    threshold floors).  Real wall clock; compilation is warmed out of
    the measured window.  All three are bit-identical per request by
    the conformance contract (tests/test_server.py), so the derived
    column is purely a latency/occupancy story."""
    from repro.configs import get_bundle
    from repro.core.engine import RetrievalSpec
    from repro.core.serve import ThresholdState
    from repro.serve import (CatalogueRegistry, Replica, ReplicaPool,
                             Request, RetrievalServer, ServerMetrics,
                             poisson_arrivals, request_stream,
                             run_open_loop)
    from repro.serve.queue import Batch

    n_req, rate = (24, 400.0) if _SMOKE else \
        ((120, 600.0) if fast else (600, 1000.0))
    model, _, rng = get_bundle("two-tower-retrieval-jpq").make_smoke()
    params = model.init_params(rng)
    spec = RetrievalSpec(kind=model.emb.cfg.kind, k=10)
    codes = params["item_emb"]["codes"].value
    hist_len = int(model.cfg.hist_len)
    buckets = tuple(sorted({max(1, hist_len // 2), hist_len}))
    hists = request_stream(n_req, n_items=int(model.cfg.n_items),
                           max_len=hist_len, seed=0)
    arrivals = poisson_arrivals(rate, n_req, seed=0)

    configs = [
        ("sync-loop", dict(max_batch=1, replicas=1, warm=False)),
        ("queue", dict(max_batch=8, replicas=1, warm=False)),
        ("queue+warm-merged", dict(max_batch=8, replicas=2, warm=True)),
    ]
    for name, c in configs:
        registry = CatalogueRegistry()
        registry.publish(codes, int(model.emb.cfg.b))
        pool = ReplicaPool(
            [Replica(model, params, k=10,
                     warm=ThresholdState(0.9) if c["warm"] else None,
                     name=f"r{i}", spec=spec)
             for i in range(c["replicas"])],
            merge_every=2 if c["warm"] else 0)
        live = registry.live()
        for rep in pool.replicas:          # compile outside the window
            for L in buckets:
                rep.serve(Batch([Request(-1, np.ones(L, np.int32))], L,
                                c["max_batch"]), live)
        pool.reset_warm()
        server = RetrievalServer(pool, registry,
                                 max_batch=c["max_batch"],
                                 max_delay=0.005, buckets=buckets,
                                 metrics=ServerMetrics(name))
        run_open_loop(server, hists, arrivals)
        server.drain()
        snap = server.metrics.snapshot()
        assert snap["requests_completed"] == n_req, snap
        lat, q = snap["latency_ms"], snap["queue_depth"]
        warm = snap["warm_hit_rate"]
        _row(f"serve_latency/{name}", f"{lat['mean'] * 1e3:.0f}",
             f"p50_ms={lat['p50']:.2f};p99_ms={lat['p99']:.2f};"
             f"qdepth_mean={q['mean']:.1f};"
             f"occupancy={snap['batch_occupancy']:.2f};"
             f"warm_hit_rate="
             f"{'n/a' if warm is None else f'{warm:.2f}'}")


# ---------------------------------------------- Pallas kernel suite

def kernels_bench(fast: bool = True):
    """Interpret-mode rows for the three training/serving kernels
    (ROADMAP: wire repro/kernels into the dryrun trajectory).  The
    derived column carries max|Δ| vs the reference — the parity claim
    CI's smoke test rides on; TPU tile timing replaces the CPU wall
    when run on real hardware."""
    from repro.kernels.embedding_bag.ops import embedding_bag
    from repro.kernels.embedding_bag.ref import embedding_bag_ref
    from repro.kernels.jpq_lookup.ops import jpq_lookup
    from repro.kernels.jpq_scores.ops import jpq_scores
    from repro.core import jpq as jpq_mod
    from repro.nn.module import KeyGen

    N, d, m, b, B = (2_000, 64, 4, 64, 8) if _SMOKE else \
        (20_000, 128, 8, 256, 16)
    pj = jpq_mod.init(KeyGen(0), N, d, m, b)
    cents, codes = pj["centroids"].value, pj["codes"].value
    h = jax.random.normal(jax.random.PRNGKey(1), (B, d))

    f_scores = jax.jit(lambda hh: jpq_scores(hh, cents, codes))
    ref_scores = jax.jit(lambda hh: jpq_mod.logits(pj, hh))
    us = time_fn(f_scores, h, iters=3, warmup=1)
    dmax = float(jnp.max(jnp.abs(f_scores(h) - ref_scores(h))))
    _row("kernels/jpq_scores/interpret", f"{us:.0f}",
         f"max_abs_err_vs_ref={dmax:.2e};N={N}")

    ids = jax.random.randint(jax.random.PRNGKey(2), (B, 8), 0, N)
    f_lookup = jax.jit(lambda ii: jpq_lookup(ii, codes, cents))
    ref_lookup = jax.jit(lambda ii: jpq_mod.lookup(pj, ii))
    us = time_fn(f_lookup, ids, iters=3, warmup=1)
    dmax = float(jnp.max(jnp.abs(f_lookup(ids) - ref_lookup(ids))))
    _row("kernels/jpq_lookup/interpret", f"{us:.0f}",
         f"max_abs_err_vs_ref={dmax:.2e};fanout=8")

    V, dd, nb, L = (1_000, 32, 64, 8) if _SMOKE else (8_192, 64, 512, 16)
    tab = jax.random.normal(jax.random.PRNGKey(3), (V, dd))
    bag_ids = jax.random.randint(jax.random.PRNGKey(4), (nb, L), 0, V)
    w = jax.random.uniform(jax.random.PRNGKey(5), (nb, L))
    f_bag = jax.jit(lambda t, i, ww: embedding_bag(t, i, ww))
    f_ref = jax.jit(lambda t, i, ww: embedding_bag_ref(t, i, ww))
    us = time_fn(f_bag, tab, bag_ids, w, iters=3, warmup=1)
    dmax = float(jnp.max(jnp.abs(f_bag(tab, bag_ids, w)
                                 - f_ref(tab, bag_ids, w))))
    _row("kernels/embedding_bag/interpret", f"{us:.0f}",
         f"max_abs_err_vs_ref={dmax:.2e};nnz={nb * L}")


# --------------------------------------- compressed gradient exchange

def grad_exchange(fast: bool = True):
    """Elastic compressed-gradient exchange accounting: per-method
    payload bytes + exchange fraction for a SASRec-sized parameter set
    — exactly the ``payload_bytes`` / ``exchange_fraction`` rows the
    Trainer emits per step, cross-checkable against the HLO collective
    bytes (tests/test_elastic_train.py pins the equality).  The wall
    column times one exchange step on a single-device host mesh."""
    from repro.dist import compression
    from repro.launch.mesh import make_host_mesh
    from repro.models.sequential import SeqRecConfig, SeqRecModel
    from repro.nn import module as nn

    n_items = 500 if _SMOKE else 5_000
    cfg = SeqRecConfig(arch="sasrec", n_items=n_items, max_len=16,
                       d_model=32, n_layers=1, n_heads=2, d_ff=64)
    model = SeqRecModel(cfg)
    values = nn.values(model.init_params(jax.random.PRNGKey(0)))
    full = compression.payload_bytes(values, "none")
    mesh = make_host_mesh(1)
    batch = {"x": jnp.ones((8, 4), jnp.float32)}

    def loss_fn(v, b):
        lf = [x for x in jax.tree.leaves(v)
              if jnp.issubdtype(jnp.asarray(x).dtype, jnp.floating)]
        return sum(jnp.sum(x) for x in lf) * jnp.mean(b["x"])

    for method in compression.METHODS:
        pb = compression.payload_bytes(values, method)
        step = compression.make_dp_grad_fn(loss_fn, mesh, method=method)
        err = compression.zeros_error_state(values, step.n_shards)
        us = time_fn(lambda: step(values, err, batch)[0], iters=3,
                     warmup=1)
        _row(f"grad_exchange/{method}", f"{us:.0f}",
             f"payload_bytes={pb};exchange_fraction={pb / full:.4f}")

    # ---- fsdp composition: per-round wire bytes from the lowered HLO
    # (docs/sharding.md byte model).  The dp collect all-gathers every
    # device's contribution stack (~V x payload on the wire); the fsdp
    # collect's tiled all-to-all ships one payload per round split
    # across devices, plus one param all-gather per *step*.  Measured
    # on a V-row-divisible toy so every float leaf shards and the wire
    # numbers are clean (SASRec's ragged leading dims would leave some
    # leaves replicated and blur the ratio).
    from repro.dist.hlo import collective_bytes
    D, V = jax.device_count(), 8
    w_fs = {"w": jnp.zeros((1024, 32), jnp.float32),
            "b": jnp.zeros((3,), jnp.float32)}
    batch_fs = {"x": jnp.zeros((16, 1024), jnp.float32),
                "y": jnp.zeros((16, 32), jnp.float32)}

    def loss_fs(vals, bt):
        pred = bt["x"] @ vals["w"] + vals["b"][:1]
        return jnp.mean((pred - bt["y"]) ** 2)

    mesh_f = make_host_mesh(D)

    if V % D == 0:
        def _collect_bytes(fn, vals):
            err = compression.zeros_error_state(w_fs, V)
            e_r = jax.tree.map(lambda x: x[np.arange(D)], err)
            b_r = jax.tree.map(
                lambda x: x.reshape((V, x.shape[0] // V) + x.shape[1:]),
                batch_fs)
            vals_full = fn.gather(vals) if fn.fsdp else vals
            hlo = fn.collect.lower(vals_full, e_r, b_r, None,
                                   jnp.int32(0)).compile().as_text()
            return collective_bytes(hlo)["per_op_bytes"]

        for method in compression.METHODS:
            pb = compression.payload_bytes(w_fs, method)
            f_dp = compression.make_dp_grad_fn(
                loss_fs, mesh_f, method=method, accum_shards=V)
            f_fs = compression.make_dp_grad_fn(
                loss_fs, mesh_f, method=method, accum_shards=V,
                fsdp=True)
            ag = _collect_bytes(f_dp, w_fs).get("all-gather", 0)
            vals_s = jax.device_put(
                w_fs, compression.fsdp_shardings(w_fs, mesh_f, V))
            a2a = _collect_bytes(f_fs, vals_s).get("all-to-all", 0)
            err_s = compression.zeros_error_state(w_fs, V)
            err_s = jax.device_put(err_s, jax.tree.map(
                lambda _: jax.sharding.NamedSharding(
                    mesh_f, compression.dp_partition_spec(mesh_f)),
                err_s))
            us = time_fn(lambda: f_fs(vals_s, err_s, batch_fs)[0],
                         iters=3, warmup=1)
            _row(f"grad_exchange/fsdp/{method}", f"{us:.0f}",
                 f"alltoall_bytes_per_round={a2a};"
                 f"dp_allgather_bytes={ag};"
                 f"reduction={ag / max(a2a, 1):.1f}x;"
                 f"payload_bytes={pb}")

    # ---- overlap schedules: serial oracle vs double-buffered dispatch
    # vs backward-overlapped, dp and fsdp, V in {4, 8} (method int8 —
    # the schedule only matters when a payload collective is worth
    # hiding).  All modes dispatch the identical compiled stage pair,
    # so the wire bytes per step are mode-invariant; the wall column is
    # the whole point of the row.  Pinned to a 2-device mesh so every
    # step runs V/2 >= 2 host rounds — on the full bench mesh (D=8,
    # V=8) there is exactly one round per step and no schedule surface
    # to measure.
    D_ov = 2 if jax.device_count() >= 2 else 1
    mesh_ov = make_host_mesh(D_ov)
    pb = compression.payload_bytes(w_fs, "int8")
    for V_ov in (4, 8):
        for fsdp_ov in (False, True):
            vals_ov = (jax.device_put(w_fs, compression.fsdp_shardings(
                w_fs, mesh_ov, V_ov)) if fsdp_ov else w_fs)
            err_ov = compression.zeros_error_state(w_fs, V_ov)
            for mode in compression.OVERLAP_MODES:
                fn = compression.make_dp_grad_fn(
                    loss_fs, mesh_ov, method="int8",
                    accum_shards=V_ov, fsdp=fsdp_ov, overlap=mode)
                us = time_fn(
                    lambda: fn(vals_ov, err_ov, batch_fs)[0],
                    iters=5, warmup=2)
                wire = pb * (V_ov // D_ov if fsdp_ov else V_ov)
                _row(f"grad_exchange/overlap/"
                     f"{'fsdp' if fsdp_ov else 'dp'}/V{V_ov}/{mode}",
                     f"{us:.0f}",
                     f"wire_bytes_per_step={wire};"
                     f"rounds={V_ov // D_ov};payload_bytes={pb}")


# ----------------------------------------------------------- roofline

def roofline():
    """§Roofline table from the dry-run JSONs (run dryrun first)."""
    root = os.path.join(os.path.dirname(__file__), "..",
                        "experiments", "dryrun")
    for path in sorted(glob.glob(os.path.join(root, "*", "*.json"))):
        with open(path) as f:
            rec = json.load(f)
        tag = f"roofline/{rec['mesh']}/{rec['arch']}/{rec['shape']}"
        if "skipped" in rec:
            _row(tag, None, "skipped")
            continue
        if "error" in rec:
            _row(tag, None, f"ERROR:{rec['error'][:50]}")
            continue
        t = rec["roofline_terms_s"]
        _row(tag, None,
             f"compute={t['compute_s']:.2e};memory={t['memory_s']:.2e};"
             f"collective={t['collective_s']:.2e};"
             f"bottleneck={rec['bottleneck']}")


# ------------------------------------------- semantic-ID generative head

def semantic_decode_bench(fast: bool = True):
    """Semantic-ID generative retrieval (core.semantic): host trie
    build, constrained-beam decode latency across beam widths (the
    per-step ``[B, W, b]`` gather is the cost driver), exhaustive-beam
    parity vs the materialise chain, and the served A/B — NDCG@10 /
    HR@10 + latency for the semantic head vs the fused-pruned score
    head on the SAME trained checkpoint (docs/serving.md)."""
    import functools
    import time as _time

    from repro.core import engine as engine_mod
    from repro.core import semantic

    # ---- micro: synthetic catalogue, scaling beam width
    B, m, b, k = (8, 4, 64, 10) if _SMOKE else (32, 8, 64, 10)
    N = 5_000 if _SMOKE else 100_000
    key = jax.random.PRNGKey(0)
    codes = np.asarray(jax.random.randint(key, (N, m), 0, b, jnp.int32))
    part = jax.random.normal(jax.random.fold_in(key, 1), (B, m, b),
                             jnp.float32)
    t0 = _time.perf_counter()
    idx = semantic.build_code_index(codes, b)
    build_us = (_time.perf_counter() - t0) * 1e6
    _row(f"semantic/N={N}/index_build", f"{build_us:.0f}",
         f"n_paths={idx.n_paths};max_leaf={idx.max_leaf}")
    for W in ((16, 64) if _SMOKE else (16, 64, 256)):
        f = jax.jit(functools.partial(semantic.semantic_decode, index=idx,
                                      k=k, beams=W))
        us = time_fn(f, part, iters=5, warmup=1)
        _row(f"semantic/N={N}/decode_W={W}", f"{us:.0f}",
             f"gather_elems={B * min(W, idx.n_paths) * b}")

    # ---- exhaustive-beam parity on a catalogue small enough to keep
    # every path alive (the tests pin bit-match; the row records it ran)
    N2 = 1_000 if _SMOKE else 2_000
    codes2 = np.asarray(jax.random.randint(jax.random.fold_in(key, 2),
                                           (N2, 4), 0, 16, jnp.int32))
    part2 = jax.random.normal(jax.random.fold_in(key, 3), (B, 4, 16),
                              jnp.float32)
    idx2 = semantic.build_code_index(codes2, 16)

    def _mat(p2, c2):            # the jpq.logits accumulation chain
        c = jnp.asarray(c2).astype(jnp.int32)
        s = p2[..., 0, :][..., c[:, 0]]
        for j in range(1, c.shape[1]):
            s = s + p2[..., j, :][..., c[:, j]]
        return jax.lax.top_k(s, k)
    rv, ri = jax.jit(_mat)(part2, codes2)
    f_ex = jax.jit(functools.partial(semantic.semantic_decode, index=idx2,
                                     k=k, beams=None))
    ev_, ei = f_ex(part2)
    exact = bool(np.array_equal(np.asarray(ev_), np.asarray(rv))
                 and np.array_equal(np.asarray(ei), np.asarray(ri)))
    us_ex = time_fn(f_ex, part2, iters=5, warmup=1)
    us_mat = time_fn(jax.jit(_mat), part2, codes2, iters=5, warmup=1)
    _row(f"semantic/N={N2}/exhaustive", f"{us_ex:.0f}",
         f"n_paths={idx2.n_paths};exact_match={exact};"
         f"materialise_us={us_mat:.0f}")

    # ---- served A/B: one checkpoint, two heads (docs/serving.md table)
    data = _make_data("ml1m", fast)
    model = _variant_model("sasrec", data, "jpq-random", m=4, b=16)
    steps = 2 if _SMOKE else (150 if fast else 600)
    params, _, _ = train_seqrec(model, data, steps=steps)
    users = list(range(0, data.n_users_eff,
                       max(data.n_users_eff // 128, 1)))
    ev = data.eval_batch(users, split="test")
    seq = jnp.asarray(ev["seq"])
    target = np.asarray(ev["target"]).reshape(-1, 1)
    emb_b = int(model.emb.cfg.b)
    item_codes = params["item_emb"]["codes"].value
    n_rows = model.cfg.n_rows
    heads = [("score-fused-pruned",
              engine_mod.RetrievalSpec(kind="jpq", k=10, prune=True)),
             ("semantic-W32",
              engine_mod.RetrievalSpec(kind="semantic", k=10, beams=32)),
             ("semantic-exhaustive",
              engine_mod.RetrievalSpec(kind="semantic", k=10,
                                       beams=n_rows))]
    for name, spec in heads:
        bound = model.bind_engine(params, spec)
        if spec.prune:
            bound.engine.bind_catalogue(
                prune=engine_mod.build_prune_state(item_codes, emb_b))
        fn = jax.jit(bound.retrieve)
        _, ids = fn(seq)
        us = time_fn(fn, seq, iters=3 if _SMOKE else 10, warmup=1)
        hit = np.asarray(ids) == target              # [U, 10]
        hr = hit.any(1).mean()
        ndcg = (hit.any(1) / np.log2(np.argmax(hit, 1) + 2)).mean()
        _row(f"semantic/ab/{name}", f"{us:.0f}",
             f"ndcg10={ndcg:.4f};hr10={hr:.4f};"
             f"eval_users={len(users)};steps={steps}")


BENCHES = {
    "table2": table2_memory,
    "table45": table45_strategies,
    "fig3": fig3_grid,
    "fig4": fig4_tradeoff,
    "jpq_scoring": jpq_scoring,
    "jpq_topk": jpq_topk_bench,
    "serve_latency": serve_latency,
    "semantic_decode": semantic_decode_bench,
    "kernels": kernels_bench,
    "grad_exchange": grad_exchange,
    "roofline": roofline,
}


def main(argv=None) -> None:
    global _SMOKE, _JSON
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help=f"one of {sorted(BENCHES)}")
    ap.add_argument("--full", action="store_true",
                    help="full-scale runs (slow; default is fast mode)")
    ap.add_argument("--smoke", action="store_true",
                    help="seconds-scale sizes (the CI smoke test)")
    ap.add_argument("--json", action="store_true",
                    help="emit one JSON array of rows instead of CSV")
    args = ap.parse_args(argv)
    _SMOKE, _JSON = args.smoke, args.json
    fast = not args.full
    if not _JSON:
        print("name,us_per_call,derived")
    for name, fn in BENCHES.items():
        if args.only and name != args.only:
            continue
        fn(fast) if fn.__code__.co_argcount else fn()
    if _JSON:
        print(json.dumps(_ROWS))


if __name__ == "__main__":
    main()

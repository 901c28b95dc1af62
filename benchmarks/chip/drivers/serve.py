"""Open-loop serving cell: single-user requests at a fixed Poisson rate
through the program's ``RetrievalServer`` (``submit`` / ``pump``), its
replica pool and the pruned PQTopK sweep.

Latency runs from a request's due time in the arrival schedule to the
moment its result is on the host, so a stall that delays later
submissions counts against them.  How late the generator submitted is
reported beside it.

Correctness: after the window, a sample of finished requests drawn from
the seed, the longest history among them, is scored by the plain
reference at float32 ``HIGHEST`` from the last item of each history,
left-padded to its bucket as in training (SASRec's next-item protocol);
the number compared is the widest gap by which a served item's
reference score lies below the reference's score at the same rank.
"""
from __future__ import annotations

import functools
import gc
import time

import numpy as np

import jax
import jax.numpy as jnp

from chip import harness, weights
from chip.traffic import arrivals, sessions

SAMPLE = 24          # requests compared: 2,400 served items at k = 100
GRACE_S = 60.0       # how long past the window a due answer may take


class SpanPool:
    """The program's replica pool inside the benchmark's own span: the
    wall time of each ``serve`` and the moment its results are on the
    host."""

    def __init__(self, pool, spans, clock, fault=None):
        self.pool = pool
        self.spans = spans
        self.clock = clock
        self.fault = fault
        self.done = {}
        # (t0, t1, n_real, max_batch, bucket_len, skipped, total)
        self.batches = []

    def serve(self, batch, version):
        t0 = self.clock()
        with self.spans.span("bench.pool_serve"):
            results, summary = self.pool.serve(batch, version)
        t1 = self.clock()
        if self.fault == "answer" and results:
            ids = results[0].ids           # an item the list lacks, first
            ids[0] = np.setdiff1d(np.arange(1, ids.size + 2), ids)[0]
        self.batches.append((t0, t1, batch.n_real, batch.max_batch,
                             batch.bucket_len, summary["skipped"],
                             summary["total"]))
        for r in results:
            self.done[r.rid] = t1
        return results, summary

    def reset_warm(self):
        self.pool.reset_warm()

    def evict_retired(self, keep):
        return self.pool.evict_retired(keep)


def bucket_of(buckets, n: int) -> int:
    for b in buckets:
        if n <= b:
            return b
    return buckets[-1]


def padded(hist: np.ndarray, L: int) -> np.ndarray:
    """The next-item protocol for one request in a bucket of length L,
    as the program trains: pad first, then the most recent L items, so
    that the last position holds the last item."""
    out = np.zeros(L, np.int32)
    h = hist[-L:]
    out[L - h.size:] = h
    return out


class Cell:
    """One serving cell.  ``fault`` "answer" alters one served item of
    every batch where the pool hands it back (harness tests only)."""

    def __init__(self, *, config: dict, traffic: dict, seed: int, spans,
                 fault=None):
        self.config = config
        self.traffic = traffic
        self.seed = int(seed)
        self.spans = spans
        self.fault = fault
        self.ref = harness.reference(config)
        self.rate = float(traffic["rate"])
        self.k = int(traffic["k"])
        self.buckets = tuple(sorted(traffic["buckets"]))
        self.clock = time.perf_counter

    # ---------------------------------------------------------- set-up
    def setup(self) -> None:
        from repro.core.assign import popularity_permutation
        from repro.core.engine import RetrievalSpec
        from repro.serve import (CatalogueRegistry, Replica, ReplicaPool,
                                 RetrievalServer)
        from repro.serve.queue import Batch, Request
        cfg, tr = self.config, self.traffic
        with self.spans.span("bench.setup_data"):
            cat, items, lengths, codes = weights.corpus_and_codes(
                cfg, self.seed)
        self.cat = cat
        n_rows = cfg["n_items"] + 2
        perm = None
        if tr["perm"]:
            perm = popularity_permutation(
                weights.popularity_counts(items, lengths, n_rows))
        with self.spans.span("bench.setup_weights"):
            self.values = self.ref.make_values(cfg, codes, self.seed)
            model, params = weights.program_model(cfg, self.values)
        with self.spans.span("bench.setup_catalogue"):
            registry = CatalogueRegistry(prune=tr["prune"])
            registry.publish(params["item_emb"]["codes"].value, cfg["b"],
                             perm=perm)
        live = registry.live()
        self.block_n = live.state.block_n if live.state is not None else 0
        spec = RetrievalSpec(kind="jpq", k=self.k, prune=tr["prune"])
        pool = ReplicaPool([Replica(model, params, k=self.k, spec=spec)])
        # warm up every bucket this traffic's lengths land in
        used = sorted({bucket_of(self.buckets, n) for n in
                       range(tr["min_len"], tr["max_len"] + 1)})
        with self.spans.span("bench.setup_warmup"):
            for L in used:
                for _ in range(2):
                    pool.serve(Batch([Request(-1, np.ones(L, np.int32))],
                                     L, tr["max_batch"]), live)
        pool.reset_warm()
        self.pool = SpanPool(pool, self.spans, self.clock, self.fault)
        self.server = RetrievalServer(
            self.pool, registry, max_batch=tr["max_batch"],
            max_delay=tr["max_delay_ms"] / 1e3, buckets=self.buckets,
            clock=self.clock)

    def prepare(self, seconds: float) -> None:
        """The seeded schedule of a window of ``seconds``: due times
        and histories, made before the window opens."""
        tr = self.traffic
        due, lengths = arrivals.schedule(self.rate, seconds, tr["min_len"],
                                         tr["max_len"], self.seed)
        items, lengths = sessions.sessions(
            self.cat, due.size, min_len=tr["min_len"],
            max_len=tr["max_len"], stay_prob=self.config["data"]["stay_prob"],
            rng=np.random.default_rng([self.seed, 2]), lengths=lengths)
        self.due = due
        self.hists = [items[i, :lengths[i]].astype(np.int32)
                      for i in range(due.size)]

    # ---------------------------------------------------------- window
    def window(self, seconds: float, clock) -> tuple:
        due, hists = self.due, self.hists
        n = due.size
        self.rid = np.full(n, -1, np.int64)
        self.late = np.zeros(n)
        server, spans = self.server, self.spans
        i = 0
        self.depths = []           # queue depth at each whole second
        gc_pauses = []
        gc_clock = {}

        def on_gc(phase, info):
            if phase == "start":
                gc_clock["t"] = clock()
            elif "t" in gc_clock:
                gc_pauses.append((info["generation"],
                                  clock() - gc_clock.pop("t")))

        gc.callbacks.append(on_gc)
        t0 = clock()
        while True:
            now = clock() - t0
            if len(self.depths) < int(min(now, seconds)):
                self.depths.append(server.in_flight())
            if i < n and due[i] <= now:
                with spans.span("bench.submit"):
                    while i < n and due[i] <= now:
                        self.rid[i] = server.submit(hists[i])
                        self.late[i] = now - due[i]
                        i += 1
            with spans.span("bench.pump"):
                served = server.pump()
            if i >= n and server.in_flight() == 0:
                break
            if now > seconds + GRACE_S:
                break
            if served == 0:
                nxt = t0 + due[i] if i < n else float("inf")
                dl = server.next_deadline()
                wait = min(nxt, dl if dl is not None else float("inf")) \
                    - clock()
                if wait > 0:
                    with spans.span("bench.wait_arrival"):
                        time.sleep(min(wait, 1e-3))
        t1 = clock()
        gc.callbacks.remove(on_gc)
        self.gc_max = max((p for _, p in gc_pauses), default=0.0)
        self.gc_full = sum(g == 2 for g, _ in gc_pauses)
        done = np.array([self.pool.done.get(int(r), np.nan)
                         for r in self.rid])
        self.latency = done - (t0 + due)          # nan: never answered
        self.in_window = int(np.sum(done - t0 <= seconds))
        return t0, min(t1, t0 + seconds)

    def latency_ms(self) -> np.ndarray:
        """Every due request's latency; one never answered is infinite."""
        return np.where(np.isnan(self.latency), np.inf, self.latency) * 1e3

    def end_to_end(self, t0: float, t1: float) -> dict:
        lat = self.latency_ms()
        return {"serve_p50_ms": float(np.percentile(lat, 50)),
                "serve_done_per_s": self.in_window / (t1 - t0)}

    def counters(self) -> dict:
        snap = self.server.metrics.snapshot()
        lat = self.latency_ms()
        # the tail, for the record: host stalls make it too unsteady to
        # bound (PERF.md)
        return {"p95_ms": float(np.percentile(lat, 95)),
                "p99_ms": float(np.percentile(lat, 99)),
                "batches": snap["batches"],
                "batch_occupancy": snap["batch_occupancy"],
                "skip_fraction": snap["skip_fraction"],
                "queue_depth_max": snap["queue_depth"]["max"],
                "block_n": self.block_n,
                "late_p50_ms": float(np.percentile(self.late, 50) * 1e3),
                "late_max_ms": float(self.late.max() * 1e3),
                "late_max_at_s": float(self.due[np.argmax(self.late)]),
                "serve_max_ms": max(b - a for a, b, *_ in
                                    self.pool.batches) * 1e3,
                "gc_max_ms": self.gc_max * 1e3,
                "gc_full": self.gc_full,
                "requests": int(self.due.size),
                "batch_log": self.pool.batches}

    def attempted(self) -> int:
        return int(self.due.size)

    @property
    def failed(self) -> int:
        return int(np.sum(np.isnan(self.latency)))

    def free(self) -> None:
        self.results = self.server.results
        del self.server, self.pool

    # ----------------------------------------------------- correctness
    def sample(self) -> np.ndarray:
        """Indices of the compared requests: drawn from the seed among
        those answered, with the longest history among them."""
        ok = np.flatnonzero(~np.isnan(self.latency))
        rng = np.random.default_rng([self.seed, 3])
        pick = rng.choice(ok, size=min(SAMPLE, ok.size), replace=False)
        longest = ok[np.argmax([self.hists[j].size for j in ok])]
        return np.unique(np.concatenate([pick, [longest]]))

    def served(self, idx) -> np.ndarray:
        return np.stack([self.results[int(self.rid[j])].ids
                         for j in idx])

    def reference_scores(self, idx, mode: str = "f32"):
        """Reference scores [len(idx), n_rows] of the sampled requests,
        each from its last item, left-padded to its bucket."""
        v = jax.tree.map(jnp.asarray, self.values)
        out = np.zeros((len(idx), self.config["n_items"] + 2), np.float32)
        groups = {}
        for r, j in enumerate(idx):
            L = bucket_of(self.buckets, self.hists[j].size)
            groups.setdefault(L, []).append(r)
        for L, rows in groups.items():
            seq = np.stack([padded(self.hists[idx[r]], L) for r in rows])
            for a in range(0, len(rows), 8):
                out[rows[a:a + 8]] = np.asarray(_ref_scores(
                    self.ref, v, jnp.asarray(seq[a:a + 8]),
                    self.config["n_heads"], mode))
        return out

    def gap(self, served: np.ndarray, want: np.ndarray) -> float:
        """Widest gap, over the sample and the ranks, between the
        reference's score at a rank and its score of the item served at
        that rank.  An item served twice, or the pad or [MASK] row,
        reads as infinite."""
        n_rows = want.shape[1]
        worst = 0.0
        best = -np.sort(-want, axis=1)[:, :self.k]
        for r in range(served.shape[0]):
            ids = served[r]
            if (np.unique(ids).size != ids.size or ids.min() < 1
                    or ids.max() > n_rows - 2):
                return float("inf")
            worst = max(worst, float(np.max(best[r] - want[r, ids])))
        return worst

    def control_served(self, idx, mode: str = "fp8") -> np.ndarray:
        """The top-k the reference computed at a lower precision would
        serve for the same requests."""
        s = self.reference_scores(idx, mode)
        return np.argsort(-s, axis=1, kind="stable")[:, :self.k]

    def check(self) -> dict:
        idx = self.sample()
        return {"score_gap": self.gap(self.served(idx),
                                      self.reference_scores(idx, "f32"))}


@functools.partial(jax.jit, static_argnames=("ref", "n_heads", "mode"))
def _ref_scores(ref, v, seq, n_heads, mode):
    with jax.default_matmul_precision("highest"):
        h = ref.encode(v, seq, n_heads, mode)[:, -1]
        return ref.scores(v, h, mode)

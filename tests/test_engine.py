"""Retrieval-engine tests (core/engine.py).

* Golden parity: the legacy ``retrieve_topk`` kwargs API (now a shim)
  and the explicit spec+engine path are bit-identical — values AND
  tie-broken ids — to the materialise-then-top-k reference, across all
  three embedding kinds × {unpruned, pruned, permuted, warm,
  mesh-sharded}.
* Spec semantics: equality ⇔ hash ⇔ jit-cache entry (hypothesis), any
  field change → a distinct cache key.
* The extension seam: a dummy scorer registered HERE serves end-to-end
  through ``serve/replica.py`` with no change to any src/ module.
* Hot-swap hygiene: the engine-owned jit cache stays bounded over N
  catalogue swaps (retired versions evicted).
* Unsupported-knob combinations raise ``ValueError`` (not assert) from
  the shim, the spec, and ``sharded.fused_topk_over_codes``.
* Both launch CLIs resolve identical specs from identical flags.
"""
import dataclasses
import json

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from test_serve_path import run_subprocess

K = 7
B, N, D = 6, 2048, 16


def _make(kind):
    from repro.core import EmbeddingConfig, make_embedding
    from repro.nn.module import KeyGen
    import jax
    cfg = EmbeddingConfig(n_items=N, d=D, kind=kind, m=4, b=16)
    emb = make_embedding(cfg)
    p = emb.init(KeyGen(0))
    h = jax.random.normal(jax.random.PRNGKey(1), (B, D))
    return emb, p, h


def _reference(emb, p, h):
    """Materialise-then-top-k ground truth (= lax.top_k, stable ties)."""
    import jax
    return jax.lax.top_k(emb.logits(p, h), K)


def _assert_same(got, want, label):
    gv, gi = got[0], got[1]
    np.testing.assert_array_equal(np.asarray(gi), np.asarray(want[1]),
                                  err_msg=f"{label}: ids diverged")
    np.testing.assert_array_equal(np.asarray(gv), np.asarray(want[0]),
                                  err_msg=f"{label}: values diverged")


# ===================================================== golden parity

class TestGoldenParity:
    @pytest.mark.parametrize("kind", ["full", "jpq", "qr"])
    def test_materialise_kinds_shim_vs_engine(self, kind):
        from repro.core import engine, serve
        emb, p, h = _make(kind)
        ref = _reference(emb, p, h)
        fused = kind == "jpq"   # full/qr always materialise; also force
        # the jpq reference branch explicitly below
        _assert_same(serve.retrieve_topk(emb, p, h, k=K, fused=False),
                     ref, f"shim fused=False kind={kind}")
        spec = engine.RetrievalSpec(kind=kind, k=K, fused=False)
        eng = engine.RetrievalEngine(spec, emb, p)
        assert eng.strategy == "materialise"
        _assert_same(eng.retrieve(h), ref, f"engine kind={kind}")
        if fused:
            _assert_same(serve.retrieve_topk(emb, p, h, k=K), ref,
                         "shim fused jpq")

    def test_jpq_fused_and_pruned_shim_vs_engine(self):
        from repro.core import engine, serve
        emb, p, h = _make("jpq")
        ref = _reference(emb, p, h)

        spec = engine.RetrievalSpec(kind="jpq", k=K)
        eng = engine.RetrievalEngine(spec, emb, p)
        assert eng.strategy == "jpq-fused"
        _assert_same(eng.retrieve(h), ref, "engine fused")

        _assert_same(serve.retrieve_topk(emb, p, h, k=K, prune=True),
                     ref, "shim pruned")
        spec_p = engine.RetrievalSpec(kind="jpq", k=K, prune=True)
        eng_p = engine.RetrievalEngine(spec_p, emb, p)
        assert eng_p.strategy == "jpq-fused-pruned"
        _assert_same(eng_p.retrieve(h), ref, "engine pruned inline")

    def test_jpq_permuted_state_shim_vs_engine(self):
        from repro.core import engine, serve
        emb, p, h = _make("jpq")
        ref = _reference(emb, p, h)
        codes = p["codes"].value
        perm = np.arange(N)[::-1].copy()
        state = engine.build_prune_state(codes, emb.cfg.b, perm=perm)
        _assert_same(serve.retrieve_topk(emb, p, h, k=K, prune=state),
                     ref, "shim permuted state")
        spec = engine.RetrievalSpec(kind="jpq", k=K, prune=True,
                                    perm="catalogue")
        eng = engine.RetrievalEngine(spec, emb, p)
        assert eng.strategy == "jpq-pruned-permuted-warm"
        eng.bind_catalogue(prune=state, version=1)
        assert eng.version == 1
        _assert_same(eng.retrieve(h), ref, "engine permuted state")

    def test_jpq_warm_floor_shim_vs_engine(self):
        from repro.core import engine, serve
        emb, p, h = _make("jpq")
        ref = _reference(emb, p, h)
        # a TIGHT admissible floor: the exact final thresholds of a
        # first pruned pass (the hardest case for the demotion rule)
        _, _, stats = serve.retrieve_topk(emb, p, h, k=K, prune=True,
                                          return_stats=True)
        floor = np.asarray(stats["theta"], np.float32)
        _assert_same(
            serve.retrieve_topk(emb, p, h, k=K, prune=True, warm=floor),
            ref, "shim warm")
        spec = engine.RetrievalSpec(kind="jpq", k=K, prune=True,
                                    warm=0.9, stats=True)
        eng = engine.RetrievalEngine(spec, emb, p).bind_catalogue(
            prune=True)
        v, i, st2 = eng.retrieve(h, floor=floor)
        _assert_same((v, i), ref, "engine warm")
        assert not bool(np.asarray(st2["demoted"]).any())

    def test_mesh_sharded_engine_matches_reference(self):
        """Permuted+warm pruned engine retrieval on a 2×4 host mesh ==
        the unsharded materialise reference, bit-for-bit."""
        body = """
        import jax, json, numpy as np
        from repro import dist
        from repro.core import EmbeddingConfig, make_embedding, engine
        from repro.nn.module import KeyGen
        B, N, D, K = 8, 2048, 16, 7
        emb = make_embedding(EmbeddingConfig(n_items=N, d=D, kind="jpq",
                                             m=4, b=16))
        p = emb.init(KeyGen(0))
        h = jax.random.normal(jax.random.PRNGKey(1), (B, D))
        rv, ri = jax.lax.top_k(emb.logits(p, h), K)
        perm = np.arange(N)[::-1].copy()
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((2, 4), ("data", "model"))
        with dist.use_mesh_rules(mesh):
            state = engine.build_prune_state(p["codes"].value, emb.cfg.b,
                                             shards=4, perm=perm)
            spec = engine.RetrievalSpec(kind="jpq", k=K, prune=True,
                                        perm="catalogue", warm=0.9,
                                        stats=True)
            eng = engine.RetrievalEngine(spec, emb, p)
            eng.bind_catalogue(prune=state, version=1)
            floor = np.full((B,), -np.inf, np.float32)
            v, i, stats = jax.jit(
                lambda h, f: eng.retrieve(h, floor=f))(h, floor)
        print(json.dumps({
            "ids": bool(np.array_equal(np.asarray(i), np.asarray(ri))),
            "vals": bool(np.array_equal(np.asarray(v), np.asarray(rv))),
            "tiles": float(np.asarray(stats["total_tiles"])),
        }))
        """
        res = json.loads(run_subprocess(body).strip().splitlines()[-1])
        assert res["ids"], "mesh engine ids diverged from reference"
        assert res["vals"], "mesh engine values not bit-identical"
        assert res["tiles"] > 0


# ============================================== spec / cache semantics

_KINDS = ["jpq", "full"]
_BACKENDS = [None, "scan", "interpret"]

settings.register_profile("engine", max_examples=80, deadline=None)
settings.load_profile("engine")


@st.composite
def spec_fields(draw):
    kind = draw(st.sampled_from(_KINDS))
    fused = draw(st.booleans())
    prune = draw(st.booleans()) and fused and kind == "jpq"
    return {
        "kind": kind,
        "k": draw(st.integers(1, 50)),
        "fused": fused,
        "backend": draw(st.sampled_from(_BACKENDS)),
        "block_n": draw(st.sampled_from([None, 256, 512])),
        "prune": prune,
        "perm": (draw(st.sampled_from(["none", "popularity"]))
                 if prune else "none"),
        "warm": (draw(st.sampled_from([None, 0.5, 0.9]))
                 if prune else None),
        "stats": draw(st.booleans()) and prune,
        "beams": draw(st.sampled_from([None, 16, 64])),
    }


class TestSpecSemantics:
    @given(spec_fields(), spec_fields())
    def test_equal_iff_hash_iff_cache_entry(self, fa, fb):
        from repro.core.engine import JitCache, RetrievalSpec
        sa, sb = RetrievalSpec(**fa), RetrievalSpec(**fb)
        assert (sa == sb) == (fa == fb)
        assert (hash(sa) == hash(sb)) == (sa == sb)
        cache = JitCache()
        ea = cache.get(sa, 0, 16, lambda: ("entry", "a"))
        eb = cache.get(sb, 0, 16, lambda: ("entry", "b"))
        assert (ea is eb) == (sa == sb), \
            "cache aliased two distinct specs" if ea is eb else \
            "cache split one spec into two entries"

    def test_any_field_change_distinct_cache_key(self):
        from repro.core.engine import JitCache, RetrievalSpec
        base = RetrievalSpec(kind="jpq", k=10, fused=True, backend="scan",
                             block_n=512, prune=True, perm="popularity",
                             warm=0.9, stats=True)
        variants = [
            dataclasses.replace(base, kind="full", fused=False,
                                prune=False, perm="none", warm=None,
                                stats=False),
            dataclasses.replace(base, k=11),
            dataclasses.replace(base, fused=False, prune=False,
                                perm="none", warm=None, stats=False),
            dataclasses.replace(base, backend="interpret"),
            dataclasses.replace(base, backend=None),
            dataclasses.replace(base, block_n=256),
            dataclasses.replace(base, block_n=None),
            dataclasses.replace(base, prune=False, perm="none",
                                warm=None, stats=False),
            dataclasses.replace(base, perm="none"),
            dataclasses.replace(base, perm="catalogue"),
            dataclasses.replace(base, warm=0.5),
            dataclasses.replace(base, warm=None),
            dataclasses.replace(base, stats=False),
            dataclasses.replace(base, beams=32),
        ]
        cache = JitCache()
        entries = [cache.get(s, 3, 16, object)
                   for s in [base] + variants]
        assert len(set(map(id, entries))) == len(entries), \
            "two different specs aliased one compiled entry"
        # version / bucket_len are part of the key too
        assert cache.get(base, 4, 16, object) is not entries[0]
        assert cache.get(base, 3, 32, object) is not entries[0]

    def test_spec_validation(self):
        from repro.core.engine import RetrievalSpec
        with pytest.raises(ValueError, match="k must be"):
            RetrievalSpec(k=0)
        with pytest.raises(ValueError, match="backend"):
            RetrievalSpec(backend="cuda")
        with pytest.raises(ValueError, match="pruned-path policy"):
            RetrievalSpec(perm="popularity", prune=False)
        with pytest.raises(ValueError, match="warm floors"):
            RetrievalSpec(warm=0.9, prune=False)
        with pytest.raises(ValueError, match="EMA decay"):
            RetrievalSpec(warm=1.0, prune=True)
        with pytest.raises(ValueError, match="stats"):
            RetrievalSpec(stats=True, prune=False)
        with pytest.raises(ValueError, match="stats"):
            RetrievalSpec(stats=True, prune=True, fused=False, kind="full")
        with pytest.raises(ValueError, match="beams"):
            RetrievalSpec(kind="semantic", beams=0)

    def test_unknown_spec_has_no_scorer(self):
        from repro.core.engine import RetrievalSpec, resolve_scorer
        import repro.core.engine as engine
        spec = RetrievalSpec(kind="nonexistent-head", k=3)
        # "nonexistent-head" is non-fused-jpq... the materialise
        # fallback claims any non-jpq kind, so exercise the error with
        # the registry's built-ins removed for a throwaway name match
        name, fn = resolve_scorer(spec)
        assert name == "materialise"
        engine.register_scorer("claims-nothing", lambda s: False,
                               lambda *a: None)
        try:
            assert resolve_scorer(spec)[0] == "materialise"
        finally:
            engine.unregister_scorer("claims-nothing")


# ===================================================== ValueError guards

class TestKnobValidation:
    def test_shim_warm_on_materialise_kind_raises(self):
        from repro.core import serve
        emb, p, h = _make("full")
        floor = np.zeros((B,), np.float32)
        with pytest.raises(ValueError, match="pruned-JPQ-fused-path"):
            serve.retrieve_topk(emb, p, h, k=K, warm=floor)

    def test_shim_stats_unpruned_raises(self):
        from repro.core import serve
        emb, p, h = _make("jpq")
        with pytest.raises(ValueError, match="stats"):
            serve.retrieve_topk(emb, p, h, k=K, return_stats=True)

    def test_sharded_warm_or_stats_without_prune_raises(self):
        from repro.core import jpq as _jpq
        from repro.core import sharded
        emb, p, h = _make("jpq")
        part = _jpq.partial_scores(p, h)
        codes = p["codes"].value
        floor = np.zeros((B,), np.float32)
        with pytest.raises(ValueError, match="pruned-path features"):
            sharded.fused_topk_over_codes(part, codes, K, warm=floor)
        with pytest.raises(ValueError, match="pruned-path features"):
            sharded.fused_topk_over_codes(part, codes, K,
                                          return_stats=True)

    def test_state_bound_to_unpruned_spec_raises(self):
        from repro.core import engine
        emb, p, _ = _make("jpq")
        state = engine.build_prune_state(p["codes"].value, emb.cfg.b)
        spec = engine.RetrievalSpec(kind="jpq", k=K, prune=False)
        with pytest.raises(ValueError, match="prune=False"):
            engine.RetrievalEngine(spec, emb, p).bind_catalogue(
                prune=state)

    def test_replica_requires_bind_engine(self):
        from repro.serve.replica import Replica
        with pytest.raises(TypeError, match="bind_engine"):
            Replica(object(), {}, k=5)


# =============================================== warm-policy round-trip

class TestWarmRoundTrip:
    """Shim-bug regression: the ``retrieve_topk`` shims accepted a
    per-request warm floor but never recorded the warm POLICY in the
    spec they built (``spec_for`` has ``warm_decay``; the shims didn't
    pass it) — so a warm-floored request served under a spec claiming
    ``warm=None``.  Now a served floor surfaces as ``warm=0.0``
    ("externally managed floor, no EMA") and an undeliverable floor
    raises from ``spec_for`` instead of being silently dropped."""

    def test_spec_for_forwards_warm_decay(self):
        from repro.core import engine
        spec = engine.spec_for("jpq", k=K, prune=True, warm_decay=0.7)
        assert spec.prune and spec.warm == 0.7

    @pytest.mark.parametrize("kwargs", [
        dict(kind="jpq", prune=None),            # unpruned jpq
        dict(kind="jpq", prune=True, fused=False),  # non-fused
        dict(kind="full", prune=True),           # non-jpq never prunes
    ])
    def test_spec_for_undeliverable_warm_raises(self, kwargs):
        from repro.core import engine
        kind = kwargs.pop("kind")
        with pytest.raises(ValueError, match="pruned-JPQ-fused-path"):
            engine.spec_for(kind, k=K, warm_decay=0.5, **kwargs)

    def test_shim_roundtrip_warm_stats_prune_combos(self):
        """Capture the spec the shim builds for every deliverable
        warm x return_stats combo on the pruned path: a served floor
        must surface as warm=0.0, stats as stats=True, and the path
        must still delegate to the fused-JPQ scorer with bit-exact
        results (the unpruned x {stats, warm} combos raise — pinned by
        test_shim_stats_unpruned_raises / the class above)."""
        from repro.core import engine, serve
        emb, p, h = _make("jpq")
        ref = _reference(emb, p, h)
        floor = np.full((B,), -np.inf, np.float32)
        captured = []

        def capture(eng, pp, hh, fl):
            captured.append((eng.spec, fl is not None))
            return engine._jpq_fused_scorer(eng, pp, hh, fl)

        engine.register_scorer(
            "capture", lambda s: s.kind == "jpq" and s.prune, capture)
        try:
            for warm in (None, floor):
                for stats in (False, True):
                    out = serve.retrieve_topk(emb, p, h, k=K, prune=True,
                                              warm=warm,
                                              return_stats=stats)
                    _assert_same(out, ref,
                                 f"shim warm={warm is not None} "
                                 f"stats={stats}")
                    assert len(out) == (3 if stats else 2)
                    spec, saw_floor = captured[-1]
                    assert spec.prune and spec.kind == "jpq"
                    assert spec.warm == \
                        (0.0 if warm is not None else None), \
                        "served floor not recorded in the spec"
                    assert spec.stats == stats
                    assert saw_floor == (warm is not None)
        finally:
            engine.unregister_scorer("capture")
        assert len(captured) == 4

    def test_model_shim_undeliverable_warm_raises(self):
        """The model-level shim copies reconcile the same way."""
        from repro.configs import get_bundle
        model, batch, rng = get_bundle(
            "two-tower-retrieval-jpq").make_smoke()
        params = model.init_params(rng)
        req = {k: v for k, v in batch.items()
               if k not in ("label", "labels")}
        # spec_for raises before the floor is ever traced, so any
        # non-None floor exercises the guard
        floor = np.zeros((4,), np.float32)
        with pytest.raises(ValueError, match="pruned-JPQ-fused-path"):
            model.retrieve(params, req, top_k=5, fused=False, warm=floor)


# ========================================== extension seam + hot-swap

def _smoke_server(*, prune=True, max_batch=4, spec=None, warm=None):
    from repro.configs import get_bundle
    from repro.serve import (CatalogueRegistry, Replica, ReplicaPool,
                             RetrievalServer)
    model, _, rng = get_bundle("two-tower-retrieval-jpq").make_smoke()
    params = model.init_params(rng)
    codes = params["item_emb"]["codes"].value
    hist_len = int(model.cfg.hist_len)
    registry = CatalogueRegistry(prune=prune)
    registry.publish(codes, int(model.emb.cfg.b))
    pool = ReplicaPool([Replica(model, params, k=5, spec=spec,
                                warm=warm)])
    server = RetrievalServer(pool, registry, max_batch=max_batch,
                             max_delay=0.0, buckets=(hist_len,))
    return model, params, codes, server


class TestExtensionSeam:
    def test_dummy_scorer_serves_end_to_end(self):
        """The acceptance-criteria seam: a scorer registered in THIS
        test file serves through serve/replica.py + RetrievalServer
        with no src/ module modified — exactly how the semantic-ID
        head will land (docs/engine.md)."""
        import jax
        from repro.core import engine

        calls = {"n": 0}

        def dummy_scorer(eng, p, h, floor):
            # a real (if naive) strategy: materialise + top-k, so the
            # served results are checkable against model.retrieve
            calls["n"] += 1
            return jax.lax.top_k(eng.emb.logits(p, h), eng.spec.k)

        engine.register_scorer("test-dummy",
                               lambda s: s.kind == "dummy-head",
                               dummy_scorer)
        try:
            spec = engine.RetrievalSpec(kind="dummy-head", k=5)
            model, params, _, server = _smoke_server(prune=False,
                                                     spec=spec)
            hist = np.arange(1, 9, dtype=np.int32)
            rid = server.submit(hist)
            server.drain()
            res = server.result(rid)
            assert calls["n"] > 0, "dummy scorer never dispatched"
            # bit-exact reference: same scorer, same padded batch shape
            # the replica jitted (accumulation order is shape-dependent)
            from repro.serve.queue import Batch, Request
            hist_len = int(model.cfg.hist_len)
            padded = Batch([Request(rid, hist)], hist_len,
                           server.queue.max_batch).padded_hist()
            bound = model.bind_engine(params, spec)
            ref_v, ref_i = jax.jit(bound.retrieve)(padded)
            np.testing.assert_array_equal(res.ids,
                                          np.asarray(ref_i)[0])
            np.testing.assert_array_equal(res.values,
                                          np.asarray(ref_v)[0])
            # and the materialise model API agrees up to float assoc.
            mv, mi = model.retrieve(
                params, {"user_hist": hist[None, :]}, top_k=5,
                fused=False)
            np.testing.assert_allclose(res.values, np.asarray(mv)[0],
                                       rtol=1e-5)
        finally:
            engine.unregister_scorer("test-dummy")

    def test_jit_cache_bounded_over_swaps(self):
        """Satellite: retired catalogue versions are evicted on
        hot-swap — the cache holds at most {live, draining} versions
        no matter how many times the catalogue republishes."""
        model, params, codes, server = _smoke_server(prune=True)
        Nc = codes.shape[0]
        rng = np.random.default_rng(0)

        def pump_some():
            for _ in range(3):
                server.submit(rng.integers(
                    1, int(model.cfg.n_items), 6).astype(np.int32))
            server.drain()

        pump_some()
        seen_versions = set()
        for swap in range(5):
            perm = np.roll(np.arange(Nc), swap + 1)
            server.registry.publish(codes, int(model.emb.cfg.b),
                                    perm=perm)
            pump_some()
            for rep in server.pool.replicas:
                vs = rep.cache.versions()
                assert len(vs) <= 2, \
                    f"cache kept {vs} after swap {swap}"
                seen_versions.update(vs)
        # the loop really did cycle through many versions
        assert len(seen_versions) >= 5
        for rep in server.pool.replicas:
            assert len(rep.cache) <= 2 * 1    # ≤ versions × buckets


# ============================================================ CLI specs

class TestCliSpecParity:
    # prune is pinned in each set: its DEFAULT is the one documented
    # per-CLI difference (test_defaults_differ_only_in_prune)
    FLAG_SETS = [
        ["--prune"],
        ["--no-prune"],
        ["--no-fused"],   # degrades prune identically on both
        ["--prune", "--perm", "--warm", "--top-k", "7"],
        ["--prune", "--warm", "0.8"],
        ["--prune", "--warm-theta", "0.7", "--perm"],
        ["--no-prune", "--top-k", "3"],
        ["--head", "semantic", "--beams", "48"],
        ["--head", "semantic", "--prune", "--warm"],  # cluster degrades
    ]

    def test_both_clis_resolve_identical_specs(self):
        from repro.core import engine
        from repro.launch import serve as serve_cli
        from repro.launch import server as server_cli
        for flags in self.FLAG_SETS:
            a = serve_cli.build_parser().parse_args(flags)
            b = server_cli.build_parser().parse_args(flags)
            sa = engine.spec_from_args(a, kind="jpq")
            sb = engine.spec_from_args(b, kind="jpq")
            assert sa == sb and hash(sa) == hash(sb), \
                f"CLIs drifted on {flags}: {sa} vs {sb}"

    def test_warm_theta_alias(self):
        from repro.launch import serve as serve_cli
        from repro.launch import server as server_cli
        for cli in (serve_cli, server_cli):
            args = cli.build_parser().parse_args(
                ["--warm-theta", "0.7"])
            assert args.warm == 0.7
            args = cli.build_parser().parse_args(["--warm"])
            assert args.warm == 0.9

    def test_defaults_differ_only_in_prune(self):
        """The documented per-CLI defaults: the batch loop serves
        unpruned, the request server pruned; everything else resolves
        identically."""
        from repro.core import engine
        from repro.launch import serve as serve_cli
        from repro.launch import server as server_cli
        a = serve_cli.build_parser().parse_args([])
        b = server_cli.build_parser().parse_args([])
        sa = engine.spec_from_args(a, kind="jpq", k=10)
        sb = engine.spec_from_args(b, kind="jpq", k=10)
        assert not sa.prune and sb.prune
        assert dataclasses.replace(sb, prune=False, stats=False) == sa

    def test_non_jpq_kind_degrades_prune_cluster(self):
        from repro.core import engine
        from repro.launch import serve as serve_cli
        args = serve_cli.build_parser().parse_args(
            ["--prune", "--perm", "--warm"])
        spec = engine.spec_from_args(args, kind="full")
        assert spec == engine.RetrievalSpec(kind="full", k=10,
                                            stats=False)

"""End-to-end serve-path tests: the fused PQTopK retrieval entrypoint
against the materialise-then-top-k reference, unsharded and on an
8-device host mesh (subprocess, so XLA_FLAGS is set before jax init),
plus unit tests for the serve-loop request generator.
"""
import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def run_subprocess(body: str, devices: int = 8) -> str:
    code = ("import os\n"
            f"os.environ['XLA_FLAGS'] = "
            f"'--xla_force_host_platform_device_count={devices}'\n"
            + textwrap.dedent(body))
    env = dict(os.environ, PYTHONPATH=SRC)
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=500)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


class TestRetrieveTopk:
    def test_fused_matches_reference_unsharded(self):
        import jax
        from repro.configs import get_bundle
        model, batch, rng = get_bundle("two-tower-retrieval-jpq") \
            .make_smoke()
        p = model.init_params(rng)
        vf, idf = jax.jit(
            lambda p, b: model.retrieve(p, b, top_k=7))(p, batch)
        vr, idr = jax.jit(
            lambda p, b: model.retrieve(p, b, top_k=7, fused=False))(
                p, batch)
        np.testing.assert_array_equal(np.asarray(idf), np.asarray(idr))
        np.testing.assert_array_equal(np.asarray(vf), np.asarray(vr))

    def test_full_table_kind_unaffected(self):
        from repro.configs import get_bundle
        model, batch, rng = get_bundle("two-tower-retrieval").make_smoke()
        p = model.init_params(rng)
        v, i = model.retrieve(p, batch, top_k=5)
        assert v.shape == i.shape == (batch["user_hist"].shape[0], 5)

    def test_fused_hlo_has_no_materialised_score_buffer(self):
        """The acceptance check: serve-time memory must not contain a
        [B, n_items] score matrix on the fused path (it must on the
        reference path — that is what it replaces).  Catalogue must
        span several blocks for the check to mean anything."""
        import jax
        import re
        import jax.numpy as jnp
        from repro.core import EmbeddingConfig, make_embedding, serve
        from repro.nn.module import KeyGen
        B, N, d = 8, 4096, 32
        emb = make_embedding(EmbeddingConfig(n_items=N, d=d, kind="jpq",
                                             m=4, b=16))
        p = emb.init(KeyGen(0))
        h = jax.random.normal(jax.random.PRNGKey(1), (B, d))
        pat = re.compile(rf"f32\[{B},{N}\]")
        txt_f = jax.jit(
            lambda p, h: serve.retrieve_topk(emb, p, h, k=5,
                                             block_n=512)) \
            .lower(p, h).compile().as_text()
        txt_r = jax.jit(
            lambda p, h: serve.retrieve_topk(emb, p, h, k=5,
                                             fused=False)) \
            .lower(p, h).compile().as_text()
        assert not pat.search(txt_f), "fused path materialised [B, N]"
        assert pat.search(txt_r), "reference path should materialise"
        # and the fused result is still exact
        vf, if_ = serve.retrieve_topk(emb, p, h, k=5, block_n=512)
        vr, ir = serve.retrieve_topk(emb, p, h, k=5, fused=False)
        np.testing.assert_array_equal(np.asarray(if_), np.asarray(ir))
        np.testing.assert_array_equal(np.asarray(vf), np.asarray(vr))

    def test_fused_sharded_matches_unsharded_reference(self):
        """two-tower-retrieval-jpq through retrieve_topk on an 8-device
        host mesh: fused+sharded ids/values == unsharded reference,
        bit-for-bit."""
        body = """
        import jax, json, numpy as np
        from repro import dist
        from repro.configs import get_bundle
        model, batch, rng = get_bundle("two-tower-retrieval-jpq").make_smoke()
        p = model.init_params(rng)
        vr, ir = jax.jit(lambda p, b: model.retrieve(p, b, top_k=7,
                                                     fused=False))(p, batch)
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((8,), ("model",))
        with dist.use_mesh_rules(mesh):
            vf, if_ = jax.jit(lambda p, b: model.retrieve(p, b,
                                                          top_k=7))(p, batch)
        print(json.dumps({
            "ids": bool(np.array_equal(np.asarray(if_), np.asarray(ir))),
            "vals": bool(np.array_equal(np.asarray(vf), np.asarray(vr))),
        }))
        """
        res = json.loads(run_subprocess(body).strip().splitlines()[-1])
        assert res["ids"], "sharded fused ids diverged from reference"
        assert res["vals"], "sharded fused values not bit-identical"

    def test_pruned_matches_reference_unsharded(self):
        """Score-bound pruning through the whole TwoTower serve entry:
        bit-identical to the materialise reference."""
        import jax
        from repro.configs import get_bundle
        model, batch, rng = get_bundle("two-tower-retrieval-jpq") \
            .make_smoke()
        p = model.init_params(rng)
        vr, ir = jax.jit(
            lambda p, b: model.retrieve(p, b, top_k=7, fused=False))(
                p, batch)
        vp, ip = jax.jit(
            lambda p, b: model.retrieve(p, b, top_k=7, prune=True))(
                p, batch)
        np.testing.assert_array_equal(np.asarray(ip), np.asarray(ir))
        np.testing.assert_array_equal(np.asarray(vp), np.asarray(vr))

    def test_pruned_sharded_matches_unsharded_reference(self):
        """Pruned + sharded (per-shard thresholds) on a 2x4 (data,
        model) mesh == unsharded materialised reference, bit-for-bit."""
        body = """
        import jax, jax.numpy as jnp, numpy as np, json
        from repro import dist
        from repro.core import sharded
        from repro.kernels.jpq_topk.ref import jpq_topk_lut_ref
        key = jax.random.PRNGKey(0)
        part = jax.random.normal(jax.random.fold_in(key, 1), (8, 4, 16))
        codes = jax.random.randint(jax.random.fold_in(key, 2), (512, 4),
                                   0, 16, jnp.int32)
        rv, ri = jpq_topk_lut_ref(part, codes, 9)
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((2, 4), ("data", "model"))
        with dist.use_mesh_rules(mesh):
            v, i = jax.jit(lambda pp, cc: sharded.fused_topk_over_codes(
                pp, cc, 9, prune=True))(part, codes)
        print(json.dumps({
            "ids": bool(np.array_equal(np.asarray(i), np.asarray(ri))),
            "vals": bool(np.array_equal(np.asarray(v), np.asarray(rv))),
        }))
        """
        res = json.loads(run_subprocess(body).strip().splitlines()[-1])
        assert res["ids"], "pruned sharded ids diverged from reference"
        assert res["vals"], "pruned sharded values not bit-identical"

    def test_fused_topk_over_codes_data_model_mesh(self):
        """LUT-level sharded entrypoint on a 2x4 (data, model) mesh."""
        body = """
        import jax, jax.numpy as jnp, numpy as np, json
        from repro import dist
        from repro.core import sharded
        from repro.kernels.jpq_topk.ref import jpq_topk_lut_ref
        key = jax.random.PRNGKey(0)
        part = jax.random.normal(jax.random.fold_in(key, 1), (8, 4, 16))
        codes = jax.random.randint(jax.random.fold_in(key, 2), (512, 4),
                                   0, 16, jnp.int32)
        rv, ri = jpq_topk_lut_ref(part, codes, 9)
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((2, 4), ("data", "model"))
        with dist.use_mesh_rules(mesh):
            v, i = jax.jit(lambda pp, cc:
                           sharded.fused_topk_over_codes(pp, cc, 9))(
                               part, codes)
        print(json.dumps({
            "ids": bool(np.array_equal(np.asarray(i), np.asarray(ri))),
            "vals": bool(np.array_equal(np.asarray(v), np.asarray(rv))),
        }))
        """
        res = json.loads(run_subprocess(body).strip().splitlines()[-1])
        assert res["ids"] and res["vals"]


class TestMakeRequests:
    """The serve-loop request generator must produce fresh ids per
    iteration (the old loop replayed one tiled batch, so p50/p99
    measured a cached dispatch), deterministically in the seed."""

    def _template(self):
        return {"user_hist": np.arange(1, 33).reshape(4, 8)
                .astype(np.int32),
                "dense": np.linspace(0, 1, 8).reshape(2, 4)
                .astype(np.float32)}

    def test_shapes_dtypes_and_bounds(self):
        from repro.launch.serve import make_requests
        reqs = list(make_requests(self._template(), batch_size=16,
                                  n_requests=3, seed=0))
        assert len(reqs) == 3
        for r in reqs:
            assert r["user_hist"].shape == (16, 8)
            assert r["user_hist"].dtype == np.int32
            assert r["user_hist"].min() >= 1
            assert r["user_hist"].max() <= 32
            assert r["dense"].shape == (16, 4)
            assert r["dense"].dtype == np.float32

    def test_ids_rerandomised_per_iteration(self):
        from repro.launch.serve import make_requests
        reqs = list(make_requests(self._template(), batch_size=8,
                                  n_requests=4, seed=0))
        hists = [r["user_hist"] for r in reqs]
        assert not any(np.array_equal(hists[0], h) for h in hists[1:]), \
            "request ids must differ across iterations"

    def test_deterministic_in_seed(self):
        from repro.launch.serve import make_requests
        a = list(make_requests(self._template(), 8, 2, seed=5))
        b = list(make_requests(self._template(), 8, 2, seed=5))
        c = list(make_requests(self._template(), 8, 2, seed=6))
        for ra, rb in zip(a, b):
            np.testing.assert_array_equal(ra["user_hist"],
                                          rb["user_hist"])
        assert not np.array_equal(a[0]["user_hist"], c[0]["user_hist"])

    def test_reserved_ids_never_drawn(self):
        """Retrieval ids are 1-based with row 0 = padding (and [MASK]
        for sequential heads): the uniform draw must exclude them, or
        synthetic requests ask the model about rows no real request
        contains."""
        from repro.launch.serve import make_requests
        tmpl = {"user_hist": np.arange(0, 32).reshape(4, 8)
                .astype(np.int32)}
        reqs = list(make_requests(tmpl, 16, 5, seed=0, reserved=(0, 31)))
        for r in reqs:
            assert 0 not in r["user_hist"]
            assert 31 not in r["user_hist"]
            assert r["user_hist"].min() >= 1
            assert r["user_hist"].max() <= 30

    def test_reserved_degenerate_range_falls_back(self):
        """A field whose whole observed range is reserved keeps the
        template range instead of drawing from an empty set."""
        from repro.launch.serve import make_requests
        tmpl = {"pos_item": np.zeros((4,), np.int32)}
        (req,) = make_requests(tmpl, 8, 1, seed=0, reserved=(0,))
        assert req["pos_item"].shape == (8,)

    def test_float_fields_row_sampled_not_tiled(self):
        """The old tile path concatenated template copies and truncated:
        a batch smaller than the template replayed the SAME leading rows
        every iteration and never dispatched the tail.  Rows must be
        sampled — every output row a template row, tail rows reachable."""
        from repro.launch.serve import make_requests
        rows = np.arange(20, dtype=np.float32).reshape(5, 4)
        reqs = list(make_requests({"dense": rows}, 2, 20, seed=0))
        row_set = {tuple(r) for r in rows}
        seen = set()
        for r in reqs:
            assert r["dense"].shape == (2, 4)
            for out in r["dense"]:
                assert tuple(out) in row_set
                seen.add(int(out[0]) // 4)
        assert seen.issuperset({2, 3, 4}), \
            f"tail template rows never sampled: {sorted(seen)}"

    def test_serve_loop_runs_end_to_end(self):
        """The CLI itself, fused and not, in a subprocess (real argv)."""
        env = dict(os.environ, PYTHONPATH=SRC)
        for extra in ([], ["--no-fused"]):
            out = subprocess.run(
                [sys.executable, "-m", "repro.launch.serve", "--arch",
                 "two-tower-retrieval-jpq", "--requests", "2",
                 "--batch-size", "4", "--seed", "1"] + extra,
                env=env, capture_output=True, text=True, timeout=300)
            assert out.returncode == 0, out.stderr[-2000:]
            assert "p99=" in out.stdout

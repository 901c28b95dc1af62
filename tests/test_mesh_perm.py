"""Mesh-native pruned serving conformance (docs/serving.md §pruning).

Permute-then-shard: the global popularity permutation is applied to the
catalogue rows BEFORE the row-shard split, each shard sweeps its own
rows in descending-popularity order, candidate lists carry original ids
through the per-shard id-map, and the merge is the (value desc, id asc)
total order.  On top: the cross-shard threshold exchange and the EMA
warm start (candidate floor + demotion).  Every combination must be
BIT-IDENTICAL to the unsharded materialise-then-top-k oracle — values
AND tie-broken ids — including duplicate-score and signed-zero ties;
warm floors must be admissible for ANY seed (the demotion rule).  Mesh
cases run in a subprocess so XLA_FLAGS lands before jax init.
"""
import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.assign import shard_sweep_ids
from repro.kernels.jpq_topk.ops import (jpq_topk_lut, mesh_prune_block_n,
                                        prepare_pruning)
from repro.kernels.jpq_topk.ref import jpq_topk_lut_ref

settings.register_profile("mp", max_examples=10, deadline=None)
settings.load_profile("mp")

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


class TestMeshPermConformance:
    def test_mesh_perm_pruned_warm_bit_exact(self):
        """The acceptance case: 2x4 (data, model) mesh, popularity-
        permuted permute-then-shard state, duplicate-score integer LUT
        with planted -0.0 ties — cold, warm-started (seeded from the
        previous request's θ), and adversarially over-seeded (demotion)
        sweeps all bit-identical to the unsharded materialise oracle;
        warm start skips tiles inside the pre-exchange window."""
        body = """
        import jax, jax.numpy as jnp, numpy as np, json
        from repro import dist
        from repro.core import sharded
        from repro.kernels.jpq_topk import ops as tops
        from repro.kernels.jpq_topk.ref import jpq_topk_lut_ref
        key = jax.random.PRNGKey(0)
        B, m, b, N, k, shards, bn = 6, 3, 8, 640, 37, 4, 32
        # popularity-structured codes (so bounds actually bite) with an
        # integer-quantised LUT (massive duplicate-score ties) and every
        # zero planted as -0.0 (signed-zero ties)
        rank = jax.random.permutation(jax.random.fold_in(key, 1),
                                      N).astype(jnp.int32)
        codes = jnp.clip((rank[:, None] * b) // N
                         + jax.random.randint(jax.random.fold_in(key, 2),
                                              (N, m), 0, 2),
                         0, b - 1).astype(jnp.int32)
        part = (jnp.round(-(jnp.arange(b) / b)[None, None, :] * 4.0)
                + jax.random.randint(jax.random.fold_in(key, 3),
                                     (B, m, b), -1, 2)).astype(jnp.float32)
        part = jnp.where(part == 0.0, -0.0, part)   # signed-zero ties
        canon = jnp.where(part == 0.0, 0.0, part)
        rv, ri = jpq_topk_lut_ref(canon, codes, k)
        perm = jnp.argsort(rank).astype(jnp.int32)  # sweep: popular 1st
        state = tops.prepare_pruning(codes, b, bn, perm=perm)
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((2, 4), ("data", "model"))
        res = {}
        def ex(v, i):
            return bool(np.array_equal(np.asarray(v), np.asarray(rv))
                        and np.array_equal(np.asarray(i), np.asarray(ri)))
        with dist.use_mesh_rules(mesh):
            f = jax.jit(lambda p, c: sharded.fused_topk_over_codes(
                p, c, k, prune=state, return_stats=True))
            fw = jax.jit(lambda p, c, w: sharded.fused_topk_over_codes(
                p, c, k, prune=state, warm=w, return_stats=True))
            v, i, stc = f(part, codes)
            res["cold"] = ex(v, i)
            res["t_ex"] = int(np.asarray(stc["exchange_tiles"]))
            v2, i2, stw = fw(part, codes, stc["theta"])
            res["warm"] = ex(v2, i2)
            nt_loc = N // shards // bn
            skv = np.asarray(stw["skips"]).reshape(shards, nt_loc)
            res["warm_first_window"] = float(
                skv[:, :max(res["t_ex"], 1)].sum())
            v3, i3, _ = fw(part, codes,
                           jnp.full((B,), 1e9, jnp.float32))
            res["demoted"] = ex(v3, i3)
            # identity (unpermuted) prebuilt state on the same mesh
            st_id = tops.prepare_pruning(codes, b, bn)
            v4, i4 = jax.jit(lambda p, c: sharded.fused_topk_over_codes(
                p, c, k, prune=st_id))(part, codes)
            res["identity"] = ex(v4, i4)
            # mismatched state (tiles straddle shard rows) must raise
            try:
                sharded.fused_topk_over_codes(
                    part, codes, k, prune=tops.prepare_pruning(codes, b, 96))
                res["mismatch_raises"] = False
            except ValueError:
                res["mismatch_raises"] = True
        print(json.dumps(res))
        """
        res = json.loads(run_subprocess(body).strip().splitlines()[-1])
        assert res["cold"], "cold mesh-perm sweep diverged from oracle"
        assert res["warm"], "warm mesh-perm sweep diverged from oracle"
        assert res["demoted"], "demotion rule failed to restore exactness"
        assert res["identity"], "identity prebuilt state diverged"
        assert res["mismatch_raises"], \
            "straddling PruneState must raise, not silently rebuild"
        assert res["t_ex"] > 0, "exchange point never scheduled"
        assert res["warm_first_window"] > 0, \
            "warm start skipped nothing before the threshold exchange"

    def test_model_level_warm_serve_sharded(self):
        """TwoTower.retrieve with a prebuilt permute-then-shard state +
        ThresholdState warm loop on an 8-way model mesh: every request
        bit-identical to the unsharded materialise reference, and the
        EMA seeds a finite floor after the first request."""
        body = """
        import jax, jax.numpy as jnp, numpy as np, json
        from repro import dist
        from repro.configs import get_bundle
        from repro.core import serve as serve_mod
        from repro.core.assign import popularity_permutation
        from repro.kernels.jpq_topk import ops as tops
        model, batch, rng = get_bundle("two-tower-retrieval-jpq").make_smoke()
        p = model.init_params(rng)
        codes = p["item_emb"]["codes"].value
        N = codes.shape[0]
        counts = np.zeros(N, np.int64)
        ids = np.asarray(batch["user_hist"]).reshape(-1)
        np.add.at(counts, ids[(ids >= 0) & (ids < N)], 1)
        perm = popularity_permutation(counts)
        state = tops.prepare_pruning(
            codes, model.emb.cfg.b, tops.mesh_prune_block_n(N, 8),
            perm=perm)
        vr, ir = jax.jit(lambda p, b: model.retrieve(
            p, b, top_k=7, fused=False))(p, batch)
        warm = serve_mod.ThresholdState(0.8)
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((8,), ("model",))
        ok = True
        with dist.use_mesh_rules(mesh):
            f = jax.jit(lambda p, b, w: model.retrieve(
                p, b, top_k=7, prune=state, warm=w, return_stats=True))
            for _ in range(3):
                B = batch["user_hist"].shape[0]
                v, i, stats = f(p, batch, jnp.asarray(warm.floor(B)))
                warm.update(np.asarray(stats["theta"]))
                ok = ok and bool(
                    np.array_equal(np.asarray(v), np.asarray(vr))
                    and np.array_equal(np.asarray(i), np.asarray(ir)))
        print(json.dumps({"ok": ok,
                          "seeded": warm.theta is not None}))
        """
        res = json.loads(run_subprocess(body).strip().splitlines()[-1])
        assert res["ok"], "warm sharded serve diverged from reference"
        assert res["seeded"], "ThresholdState never learned a floor"


class TestPermuteThenShardLayout:
    def test_shard_sweep_ids_matches_prepare_pruning_slices(self):
        """The assign-level layout helper and the PruneState id-map must
        agree: shard s's id-map is perm[s*L:(s+1)*L]."""
        N, shards = 480, 4
        perm = np.random.default_rng(3).permutation(N)
        layout = shard_sweep_ids(perm, shards)
        codes = jnp.asarray(np.random.default_rng(4)
                            .integers(0, 8, (N, 3)), jnp.int32)
        st_ = prepare_pruning(codes, 8, 40, perm=jnp.asarray(perm,
                                                            jnp.int32))
        np.testing.assert_array_equal(
            np.asarray(st_.ids).reshape(shards, N // shards), layout)
        # permuted codes rows == codes gathered through the id-map
        np.testing.assert_array_equal(
            np.asarray(st_.codes), np.asarray(codes)[perm])
        with pytest.raises(ValueError):
            shard_sweep_ids(perm, 7)

    def test_mesh_prune_block_n_divides(self):
        for N, shards in [(1_000_448, 16), (1_000_000, 8), (640, 4),
                          (20_000, 8)]:
            bn = mesh_prune_block_n(N, shards)
            assert (N // shards) % bn == 0, (N, shards, bn)
        # and it tracks the target when divisors allow
        assert mesh_prune_block_n(1_000_000, 8) == 6250


class TestWarmStartAdmissibility:
    """Property sweep: for ANY floor — too low, exact, too high, ±inf,
    per-query mixed — the warm-started pruned sweep must stay
    bit-identical to the materialise oracle (the demotion rule is what
    makes over-seeded floors safe)."""

    @given(st.integers(1, 300), st.sampled_from([1, 2, 4]),
           st.sampled_from([2, 16]),
           st.tuples(st.integers(1, 4), st.integers(1, 48)),
           st.booleans(), st.floats(-3.0, 3.0), st.floats(0.0, 2.0))
    def test_any_floor_is_exact(self, N, m, b, Bk, use_perm, off, scale):
        B, k = Bk
        key = jax.random.PRNGKey(N * 131 + m * 17 + B * 3 + k)
        partial = jnp.round(
            jax.random.normal(jax.random.fold_in(key, 1), (B, m, b))
            * scale)
        codes = jax.random.randint(jax.random.fold_in(key, 2), (N, m),
                                   0, b, jnp.int32)
        perm = None
        if use_perm:
            perm = jnp.asarray(np.random.default_rng(N + k)
                               .permutation(N), jnp.int32)
        canon = jnp.where(partial == 0.0, 0.0, partial)
        rv, ri = jpq_topk_lut_ref(canon, codes, k)
        theta_true = rv[:, -1]
        floors = [
            jnp.full((B,), float(off), jnp.float32),      # arbitrary
            theta_true,                                   # exact seed
            theta_true + 1.5,                             # overshoot
            theta_true - 1.5,                             # undershoot
            jnp.full((B,), jnp.inf, jnp.float32),         # degenerate
        ]
        for backend in ["scan", "interpret"]:
            for fl in floors:
                v, i = jpq_topk_lut(partial, codes, k, block_n=64,
                                    backend=backend, prune=True,
                                    perm=perm, warm=fl)
                np.testing.assert_array_equal(
                    np.asarray(v), np.asarray(rv),
                    err_msg=f"{backend} floor={fl} values")
                np.testing.assert_array_equal(
                    np.asarray(i), np.asarray(ri),
                    err_msg=f"{backend} floor={fl} ids")

    def test_exact_seed_skips_first_tiles(self):
        """Seeding with the true final θ can only skip MORE tiles than
        a cold sweep (the floor is everywhere ≥ the running θ).  The
        'first tiles prune too' property shows sharpest on an
        ASCENDING-popularity sweep — the order a tail shard of the
        permute-then-shard split sees: cold, the threshold only
        tightens at the very end, so early tiles all score; warm, they
        are dead on arrival, from tile 0."""
        N, m, b, B, k = 4096, 4, 32, 4, 32
        key = jax.random.PRNGKey(0)
        rank = jax.random.permutation(jax.random.fold_in(key, 1),
                                      N).astype(jnp.int32)
        codes = jnp.clip((rank[:, None].astype(jnp.int64) * b) // N
                         + jax.random.randint(jax.random.fold_in(key, 2),
                                              (N, m), 0, 2),
                         0, b - 1).astype(jnp.int32)
        partial = (-(jnp.arange(b) / b)[None, None, :] * 4.0
                   + 0.1 * jax.random.normal(jax.random.fold_in(key, 3),
                                             (B, m, b)))
        rv, ri = jpq_topk_lut_ref(partial, codes, k)
        for perm in (jnp.argsort(rank).astype(jnp.int32),        # pop
                     jnp.argsort(rank)[::-1].astype(jnp.int32)):  # rev
            cold = jpq_topk_lut(partial, codes, k, block_n=256,
                                prune=True, perm=perm,
                                return_stats=True)
            warm = jpq_topk_lut(partial, codes, k, block_n=256,
                                prune=True, perm=perm,
                                warm=cold[2]["theta"],
                                return_stats=True)
            for v, i, stats in (cold, warm):
                np.testing.assert_array_equal(np.asarray(v),
                                              np.asarray(rv))
                np.testing.assert_array_equal(np.asarray(i),
                                              np.asarray(ri))
            assert int(warm[2]["skipped_tiles"]) >= \
                int(cold[2]["skipped_tiles"])
        # perm is the reversed sweep here: warm kills tile 0, cold
        # cannot (θ = -inf until k candidates have been seen)
        assert int(np.asarray(warm[2]["skips"])[0]) == 1
        assert int(np.asarray(cold[2]["skips"])[0]) == 0

    def test_threshold_state_ema(self):
        from repro.core.serve import ThresholdState
        ts = ThresholdState(0.5)
        assert not np.isfinite(ts.floor(3)).any()
        ts.update(np.asarray([2.0, 4.0]))          # min -> 2.0
        assert ts.theta == 2.0
        ts.update(np.asarray([6.0, 8.0]))          # 0.5*2 + 0.5*6
        assert ts.theta == 4.0
        np.testing.assert_array_equal(ts.floor(2),
                                      np.full(2, 4.0, np.float32))
        ts.update(np.asarray([-np.inf]))           # cold request: no-op
        assert ts.theta == 4.0

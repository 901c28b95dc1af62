"""Fast single-process unit tests for the pure parts of ``repro.dist``
(the multi-device integration paths live in test_dist.py's
subprocesses) plus a smoke test for ``core.sharded.topk_over_items``."""
import types

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import sharded
from repro.dist import compression
from repro.dist.hlo import collective_bytes
from repro.dist.rules import DEFAULT_RULES, resolve_axes, use_mesh_rules


def _mesh(**shape):
    """Duck-typed stand-in: resolve_axes only reads ``mesh.shape``."""
    return types.SimpleNamespace(shape=dict(shape))


class TestResolveAxes:
    def test_batch_over_joint_pod_data(self):
        s = resolve_axes(("batch", "seq"), (8, 16),
                         _mesh(pod=2, data=2, model=2))
        assert s[0] == ("pod", "data") and s[1] is None

    def test_batch_filters_to_present_axes(self):
        s = resolve_axes(("batch",), (8,), _mesh(data=4, model=2))
        assert s[0] == "data"

    def test_width_axes_take_model(self):
        s = resolve_axes(("embed", "mlp"), (32, 64),
                         _mesh(data=4, model=2))
        assert s[0] is None and s[1] == "model"

    def test_divisibility_falls_back_to_replicated(self):
        s = resolve_axes(("vocab",), (7,), _mesh(model=4))
        assert s[0] is None

    def test_joint_axes_drop_trailing_until_divisible(self):
        # 6 % (2*2) != 0 but 6 % 2 == 0 -> keep "pod" only
        s = resolve_axes(("batch",), (6,), _mesh(pod=2, data=2))
        assert s[0] == "pod"

    def test_first_dim_wins_conflict(self):
        s = resolve_axes(("mlp", "mlp"), (8, 8), _mesh(model=2))
        assert s[0] == "model" and s[1] is None

    def test_none_and_unknown_names_replicate(self):
        s = resolve_axes((None, "code_split"), (4, 4), _mesh(model=2))
        assert s[0] is None and s[1] is None

    def test_rules_override(self):
        s = resolve_axes(("embed",), (8,), _mesh(model=2),
                         rules={"embed": ("model",)})
        assert s[0] == "model"

    def test_default_rules_cover_documented_names(self):
        table = dict(DEFAULT_RULES)
        for name in ("batch", "mlp", "heads", "vocab", "items",
                     "table", "centroid", "expert"):
            assert name in table

    def test_context_manager_installs_and_restores(self):
        from repro.dist import rules as r
        assert r._CTX.mesh is None
        m = _mesh(data=2)
        with use_mesh_rules(m, rules={"x": ("data",)}):
            assert r._CTX.mesh is m
            assert r._CTX.rules == {"x": ("data",)}
        assert r._CTX.mesh is None and r._CTX.rules is None


class TestCollectiveBytes:
    def test_counts_and_bytes(self):
        hlo = """
        %ag = f32[4,8]{1,0} all-gather(f32[1,8] %x), dims={0}
        %ag2 = f32[2,8]{1,0} all-gather(f32[1,8] %y), dims={0}
        %rs = bf16[16]{0} reduce-scatter(bf16[128] %z), dims={0}
        %fusion = f32[64] fusion(f32[64] %a), kind=kLoop
        """
        res = collective_bytes(hlo)
        assert res["per_op_bytes"]["all-gather"] == (4 * 8 + 2 * 8) * 4
        assert res["per_op_counts"]["all-gather"] == 2
        assert res["per_op_bytes"]["reduce-scatter"] == 32
        assert res["total_bytes"] == sum(res["per_op_bytes"].values())
        assert "fusion" not in res["per_op_bytes"]

    def test_async_pairs_counted_once(self):
        hlo = """
        %s = f32[8]{0} all-reduce-start(f32[8] %x), to_apply=%add
        %d = f32[8]{0} all-reduce-done(f32[8] %s)
        """
        res = collective_bytes(hlo)
        assert res["per_op_counts"]["all-reduce"] == 1
        assert res["per_op_bytes"]["all-reduce"] == 32

    def test_async_tuple_start_counts_output_only(self):
        # async tuple results alias the operand buffer; only the actual
        # output (last element) counts, matching the sync convention
        hlo = ("%s = (f32[1,8]{1,0}, f32[4,8]{1,0}) "
               "all-gather-start(f32[1,8] %x), dims={0}")
        res = collective_bytes(hlo)
        assert res["per_op_bytes"]["all-gather"] == 4 * 8 * 4

    def test_tuple_result_shapes_summed(self):
        hlo = "%t = (f32[8]{0}, f32[8]{0}) all-to-all(f32[8] %a, f32[8] %b)"
        res = collective_bytes(hlo)
        assert res["per_op_bytes"]["all-to-all"] == 64

    def test_scalar_and_empty(self):
        assert collective_bytes("")["total_bytes"] == 0
        res = collective_bytes("%r = f32[] all-reduce(f32[] %x)")
        assert res["per_op_bytes"]["all-reduce"] == 4


class TestPayloadBytes:
    def test_ratios(self):
        values = {"w": jnp.zeros(16), "b": jnp.zeros((2, 3))}
        full = compression.payload_bytes(values, "none")
        assert full == (16 + 6) * 4
        assert compression.payload_bytes(values, "bf16") * 2 == full
        assert compression.payload_bytes(values, "int8") * 4 == full

    def test_int_leaves_excluded(self):
        values = {"w": jnp.zeros(8), "codes": jnp.zeros(100, jnp.uint8)}
        assert compression.payload_bytes(values, "none") == 32


class TestTopkOverItems:
    def test_matches_lax_topk_single_device(self):
        scores = jax.random.normal(jax.random.PRNGKey(0), (4, 64))
        v, i = sharded.topk_over_items(scores, 5)
        rv, ri = jax.lax.top_k(scores, 5)
        np.testing.assert_allclose(np.asarray(v), np.asarray(rv))
        np.testing.assert_array_equal(np.asarray(i), np.asarray(ri))

    def test_matches_under_mesh_context(self):
        """One-device mesh exercises the shard_map path (shards=1)."""
        scores = jax.random.normal(jax.random.PRNGKey(1), (2, 33))
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((1,), ("model",))
        with use_mesh_rules(mesh):
            v, i = sharded.topk_over_items(scores, 3)
        rv, ri = jax.lax.top_k(scores, 3)
        np.testing.assert_allclose(np.asarray(v), np.asarray(rv))
        np.testing.assert_array_equal(np.asarray(i), np.asarray(ri))

"""Distribution layer: rules resolution, sharded training parity,
gradient compression, elastic checkpoint restore.

Multi-device tests run in subprocesses so XLA_FLAGS is set before jax
initialises (the main test process keeps the single real CPU device).
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


class TestRules:
    def test_divisibility_fallback(self):
        body = """
        import jax, json
        from repro.dist.rules import resolve_axes
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((2, 4), ("data", "model"))
        # heads=40 not divisible by model=4? 40%4==0 -> shards
        s1 = resolve_axes(("embed", "heads", "head_dim"), (64, 40, 16), mesh)
        # heads=6 not divisible by 4 -> falls back to replicated
        s2 = resolve_axes(("embed", "heads", "head_dim"), (64, 6, 16), mesh)
        # axis conflict: two dims can't share a mesh axis
        s3 = resolve_axes(("mlp", "mlp"), (8, 8), mesh)
        print(json.dumps([str(s1), str(s2), str(s3)]))
        """
        out = json.loads(run_subprocess(body).strip())
        assert "'model'" in out[0]
        assert out[1].count("model") == 0
        assert out[2].count("model") == 1      # only first dim takes it

    def test_batch_prefers_pod_data(self):
        body = """
        import jax, json
        from repro.dist.rules import resolve_axes
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
        s = resolve_axes(("batch", "seq"), (8, 16), mesh)
        print(str(s))
        """
        out = run_subprocess(body).strip()
        assert "pod" in out and "data" in out


class TestShardedTraining:
    def test_mesh_training_matches_single_device(self):
        """The same model/data trained on a 4x2 mesh and on one device
        must produce the same loss trajectory (SPMD is semantics-
        preserving)."""
        body = """
        import jax, jax.numpy as jnp, numpy as np, json
        from repro.data.sequences import SeqDataConfig, SyntheticSequences
        from repro.models.sequential import SeqRecConfig, SeqRecModel
        from repro.train.loop import Trainer, TrainConfig
        from repro.train.optimizer import OptConfig

        def losses(mesh):
            cfg = SeqRecConfig(arch="sasrec", n_items=40, max_len=8,
                               d_model=32, n_layers=1, n_heads=2, d_ff=32)
            model = SeqRecModel(cfg)
            data = SyntheticSequences(SeqDataConfig(n_users=64, n_items=40,
                                                    seq_len=8))
            tr = Trainer(model, OptConfig(lr=1e-2, kind="sgd"),
                         TrainConfig(steps=8, batch_size=8, log_every=1,
                                     eval_every=0),
                         data_fn=lambda s: data.train_batch(s, 8),
                         mesh=mesh)
            _, hist = tr.run()
            return [h["loss"] for h in hist if "loss" in h]

        from repro.launch.mesh import make_mesh
        mesh = make_mesh((4, 2), ("data", "model"))
        l_mesh = losses(mesh)
        l_one = losses(None)
        print(json.dumps([l_mesh, l_one]))
        """
        l_mesh, l_one = json.loads(run_subprocess(body).strip().splitlines()[-1])
        np.testing.assert_allclose(l_mesh, l_one, rtol=1e-3)
        assert l_mesh[-1] < l_mesh[0]

    def test_jpq_logits_shard_over_items(self):
        """Catalogue scoring with row-sharded codes compiles and matches
        the single-device result (the retrieval_cand path)."""
        body = """
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.core import jpq
        from repro.nn.module import KeyGen
        from repro.nn import module as nn
        p = jpq.init(KeyGen(0), 4096, 32, 4, 16)
        h = jax.random.normal(jax.random.PRNGKey(1), (8, 32))
        ref = jpq.logits(nn.with_values(p, nn.values(p)), h)
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((8,), ("model",))
        codes_sh = jax.device_put(p["codes"].value,
                                  NamedSharding(mesh, P("model", None)))
        p2 = {"codes": nn.P(codes_sh, p["codes"].axes),
              "centroids": p["centroids"]}
        with jax.sharding.use_mesh(mesh) if hasattr(jax.sharding, "use_mesh") else mesh:
            out = jax.jit(lambda pp, hh: jpq.logits(pp, hh))(p2, h)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-4, atol=1e-4)
        print("OK")
        """
        assert "OK" in run_subprocess(body)


class TestGradCompression:
    def test_bf16_and_int8_with_error_feedback_converge(self):
        body = """
        import jax, jax.numpy as jnp, numpy as np, json
        from repro.dist.compression import (make_dp_grad_fn,
                                            zeros_error_state,
                                            payload_bytes)
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((8,), ("data",))
        target = jnp.asarray(np.random.default_rng(0)
                             .standard_normal(16), jnp.float32)

        def loss_fn(values, batch):
            pred = batch @ values["w"]
            return jnp.mean((pred - batch @ target) ** 2)

        results = {}
        for method in ("none", "bf16", "int8"):
            values = {"w": jnp.zeros(16)}
            err = zeros_error_state(values, 8)
            gf = make_dp_grad_fn(loss_fn, mesh, method=method)
            rng = np.random.default_rng(1)
            for step in range(150):
                batch = jnp.asarray(rng.standard_normal((64, 16)),
                                    jnp.float32)
                grads, err, loss = gf(values, err, batch)
                values = jax.tree.map(lambda v, g: v - 0.05 * g,
                                      values, grads)
            results[method] = float(jnp.max(jnp.abs(values["w"] - target)))
        results["payload_none"] = payload_bytes({"w": jnp.zeros(16)}, "none")
        results["payload_int8"] = payload_bytes({"w": jnp.zeros(16)}, "int8")
        print(json.dumps(results))
        """
        res = json.loads(run_subprocess(body).strip().splitlines()[-1])
        assert res["none"] < 1e-2
        assert res["bf16"] < 3e-2          # error feedback keeps it close
        assert res["int8"] < 5e-2
        assert res["payload_int8"] * 4 == res["payload_none"]

    def test_virtual_shards_bitwise_across_mesh_sizes(self):
        """With accum_shards fixed, the exchanged gradients (and the
        error-feedback trajectory) are bit-identical on 8-, 4- and
        2-device meshes — the property elastic restore relies on.
        One slice per device per dispatch pins the per-slice numerics;
        the ordered mean over the gathered [V, ...] stack never sees
        the device count."""
        body = """
        import jax, jax.numpy as jnp, numpy as np, json
        from repro.dist.compression import (make_dp_grad_fn,
                                            zeros_error_state)
        target = jnp.asarray(np.random.default_rng(0)
                             .standard_normal(16), jnp.float32)

        def loss_fn(values, batch):
            return jnp.mean((batch @ values["w"] - batch @ target) ** 2)

        results = {}
        for method in ("none", "bf16", "int8"):
            per_mesh = []
            for d in (8, 4, 2):
                from repro.launch.mesh import make_mesh
                mesh = make_mesh((d,), ("data",))
                gf = make_dp_grad_fn(loss_fn, mesh, method=method,
                                     accum_shards=8)
                values = {"w": jnp.zeros(16)}
                err = zeros_error_state(values, 8)
                rng = np.random.default_rng(1)
                for step in range(5):
                    batch = jnp.asarray(rng.standard_normal((64, 16)),
                                        jnp.float32)
                    grads, err, loss = gf(values, err, batch)
                    values = jax.tree.map(lambda v, g: v - 0.05 * g,
                                          values, grads)
                per_mesh.append((np.asarray(values["w"]),
                                 np.asarray(err["w"])))
            w8, e8 = per_mesh[0]
            results[method] = all(
                np.array_equal(w8, w) and np.array_equal(e8, e)
                for w, e in per_mesh[1:])
        print(json.dumps(results))
        """
        res = json.loads(run_subprocess(body).strip().splitlines()[-1])
        assert res == {"none": True, "bf16": True, "int8": True}

    def test_non_float_leaves_get_treewide_safe_zero_grads(self):
        """Frozen int leaves (JPQ codebooks) come back as zero grads in
        the leaf's own shape/dtype, so ``v - lr * g`` over the whole
        tree neither crashes nor moves them."""
        body = """
        import jax, jax.numpy as jnp, numpy as np
        from repro.dist.compression import (make_dp_grad_fn,
                                            zeros_error_state)
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((4,), ("data",))
        values = {"w": jnp.ones(8),
                  "codes": jnp.arange(6, dtype=jnp.uint8)}

        def loss_fn(v, batch):
            return jnp.mean((batch @ v["w"]) ** 2)

        gf = make_dp_grad_fn(loss_fn, mesh, method="int8")
        err = zeros_error_state(values, 4)
        batch = jnp.ones((16, 8))
        grads, err, loss = gf(values, err, batch)
        assert grads["codes"].shape == values["codes"].shape
        assert grads["codes"].dtype == values["codes"].dtype
        new = jax.tree.map(lambda v, g: v - g, values, grads)
        np.testing.assert_array_equal(np.asarray(new["codes"]),
                                      np.asarray(values["codes"]))
        print("OK")
        """
        assert "OK" in run_subprocess(body)

    def test_accum_shards_must_divide(self):
        body = """
        import jax
        from repro.dist.compression import make_dp_grad_fn
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((8,), ("data",))
        try:
            make_dp_grad_fn(lambda v, b: 0.0, mesh, accum_shards=12)
            print("NO-RAISE")
        except ValueError as e:
            print("RAISED", "multiple" in str(e))
        """
        assert "RAISED True" in run_subprocess(body)


class TestElasticRestore:
    def test_checkpoint_moves_between_meshes(self):
        """Save sharded on a (4,2) mesh, restore onto (2,2) — the elastic
        rescale path (pod loss / shrink)."""
        body = """
        import tempfile, jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.ckpt import save_checkpoint, restore_checkpoint

        t = {"w": jnp.arange(64.0).reshape(8, 8),
             "m": jnp.ones((8, 8))}
        from repro.launch.mesh import make_mesh
        mesh_a = make_mesh((4, 2), ("data", "model"))
        sh_a = {"w": NamedSharding(mesh_a, P("data", "model")),
                "m": NamedSharding(mesh_a, P("data", None))}
        t_a = jax.tree.map(jax.device_put, t, sh_a)
        with tempfile.TemporaryDirectory() as d:
            save_checkpoint(d, t_a, 5)
            from repro.launch.mesh import make_mesh
            mesh_b = make_mesh((2, 2), ("data", "model"))
            sh_b = {"w": NamedSharding(mesh_b, P("data", "model")),
                    "m": NamedSharding(mesh_b, P(None, "model"))}
            restored, step = restore_checkpoint(d, t, shardings=sh_b)
            assert step == 5
            np.testing.assert_array_equal(np.asarray(restored["w"]),
                                          np.asarray(t["w"]))
            assert restored["w"].sharding.mesh.shape["data"] == 2
        print("OK")
        """
        assert "OK" in run_subprocess(body)


class TestDryrunMachinery:
    def test_collective_bytes_parser(self):
        from repro.dist.hlo import collective_bytes
        hlo = """
        %ag = f32[8,128]{1,0} all-gather(f32[1,128] %x), dims={0}
        %ar.1 = bf16[256]{0} all-reduce(bf16[256] %y), to_apply=%add
        %cp = f32[4]{0} collective-permute(f32[4] %z)
        %other = f32[999] add(f32[999] %a, f32[999] %b)
        """
        res = collective_bytes(hlo)
        assert res["per_op_bytes"]["all-gather"] == 8 * 128 * 4
        assert res["per_op_bytes"]["all-reduce"] == 512
        assert res["per_op_counts"]["collective-permute"] == 1
        assert "add" not in res["per_op_bytes"]

    def test_dryrun_single_cell_small_mesh(self):
        """End-to-end dry-run machinery on an 8-device mesh (fast)."""
        body = """
        import jax, json
        from repro.configs import get_bundle
        from repro.launch import dryrun as dr
        from repro import dist
        bundle = get_bundle("fm")
        cell = bundle.cells["serve_p99"]
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((4, 2), ("data", "model"))
        model = bundle.make_model("serve_p99")
        fn, args, donate = dr.build_cell_args(bundle, cell, model, mesh)
        with dist.use_mesh_rules(mesh):
            compiled = jax.jit(fn, donate_argnums=donate).lower(*args).compile()
        cost = compiled.cost_analysis()
        print(json.dumps({"flops": float(cost.get("flops", -1))}))
        """
        out = json.loads(run_subprocess(body).strip().splitlines()[-1])
        assert out["flops"] != 0

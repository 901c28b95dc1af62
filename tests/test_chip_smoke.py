"""chip_smoke.py's phases at tiny size on CPU, so the chip script
cannot rot between chip runs: the train phase through the Trainer, the
serve phases through the Pallas interpreter (the chip runs the same
code with the Mosaic kernel), and the script's refusal to run, or to
print a result line, without a TPU."""
import importlib.util
import os
import subprocess
import sys

import jax.numpy as jnp
import pytest

ROOT = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..")


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(ROOT, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def trained(smoke):
    return smoke.train_phase(n_items=60, d_model=32, max_len=12,
                             n_layers=1, n_heads=2, d_ff=64, m=4, b=16,
                             batch=16, steps=12, n_users=300, seed=0,
                             lr=1e-2)


class TestPhases:
    def test_train_loss_finite_and_falls(self, trained):
        _, _, _, rep = trained
        assert len(rep["losses"]) == 12
        assert rep["steps_per_s"] > 0 and rep["first_step_s"] > 0

    def test_serve_matches_reference(self, smoke, trained):
        model, params, data, _ = trained
        seqs = jnp.asarray(data.eval_batch(range(8))["seq"])
        out = smoke.serve_phase(model, params, seqs, k=5,
                                backend="interpret")
        assert set(out) == {"fused", "pruned"}
        for r in out.values():
            assert r["bitwise"] and r["ids_equal"] == 1.0
            assert not r["kernel"]          # interpreter, not Mosaic

    def test_catalogue_matches_reference(self, smoke):
        out = smoke.catalogue_phase(n_items=3000, batch=5, k=20, d=16,
                                    m=4, b=32, seed=0, backend="interpret")
        for r in out.values():
            assert r["bitwise"] and r["max_abs_dv"] == 0.0
            assert r["exact"] and r["reference_exact"]

    def test_compare_topk_rejects_a_wrong_id(self, smoke):
        import numpy as np
        scores = np.array([[3.0, 2.0, 1.0, 0.0]], np.float32)
        v = np.array([[3.0, 2.0]], np.float32)
        with pytest.raises(RuntimeError, match="does not score"):
            smoke.compare_topk(v, np.array([[0, 2]]), v,
                               np.array([[0, 1]]), scores, "t")


class TestNoAccelerator:
    def test_main_off_tpu_fails_without_result_line(self):
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        out = subprocess.run([sys.executable, "chip_smoke.py"], cwd=ROOT,
                             env=env, capture_output=True, text=True,
                             timeout=300)
        assert out.returncode != 0
        assert '"ok"' not in out.stdout
        assert "no TPU" in out.stderr


class TestCompileCache:
    """``compile_cache.enable`` uses ``$JAX_COMPILATION_CACHE_DIR`` when
    it is set and sets nothing; otherwise the checkout's fixed
    ``.jax_cache``.  In a subprocess: it changes process-wide config."""

    def _run(self, env_dir):
        code = ("import jax; from repro.launch import compile_cache as c; "
                "d = c.enable(); "
                "print(d); print(jax.config.jax_compilation_cache_dir)")
        env = {k: v for k, v in os.environ.items()
               if k != "JAX_COMPILATION_CACHE_DIR"}
        env["PYTHONPATH"] = os.path.join(ROOT, "src")
        if env_dir is not None:
            env["JAX_COMPILATION_CACHE_DIR"] = env_dir
        out = subprocess.run([sys.executable, "-c", code], env=env,
                             capture_output=True, text=True, timeout=120)
        assert out.returncode == 0, out.stderr[-2000:]
        return out.stdout.split()

    def test_env_var_wins(self, tmp_path):
        d, cfg = self._run(str(tmp_path))
        assert d == cfg == str(tmp_path)

    def test_default_is_the_checkout(self):
        d, cfg = self._run(None)
        assert d == cfg == os.path.join(os.path.abspath(ROOT), ".jax_cache")

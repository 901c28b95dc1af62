"""Training substrate: loop, checkpointing (atomic, keep-N, async,
elastic restore), metrics, data determinism."""
import os
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.ckpt import (AsyncCheckpointer, latest_step, restore_checkpoint,
                        save_checkpoint)
from repro.core import EmbeddingConfig
from repro.data.clicks import ClickDataConfig, SyntheticClicks, dien_batch
from repro.data.graphs import GraphConfig, make_graph, pad_block, \
    sample_block, to_csr
from repro.data.sequences import SeqDataConfig, SyntheticSequences
from repro.models.sequential import SeqRecConfig, SeqRecModel
from repro.train.loop import TrainConfig, Trainer
from repro.train.metrics import hr_at_k, ndcg_at_k, rank_of
from repro.train.optimizer import OptConfig


class TestCheckpoint:
    def _tree(self):
        return {"a": {"w": jnp.arange(6.0).reshape(2, 3),
                      "codes": jnp.arange(4, dtype=jnp.uint8)},
                "b": [jnp.ones(3), jnp.zeros((), jnp.int32)],
                "bf": jnp.ones(4, jnp.bfloat16)}

    def test_roundtrip_with_exotic_dtypes(self):
        t = self._tree()
        with tempfile.TemporaryDirectory() as d:
            save_checkpoint(d, t, 7)
            restored, step = restore_checkpoint(d, t)
            assert step == 7
            for a, b in zip(jax.tree.leaves(t), jax.tree.leaves(restored)):
                np.testing.assert_array_equal(np.asarray(a, np.float32)
                                              if a.dtype == jnp.bfloat16
                                              else np.asarray(a),
                                              np.asarray(b, np.float32)
                                              if a.dtype == jnp.bfloat16
                                              else np.asarray(b))
                assert a.dtype == b.dtype

    def test_keep_n_gc(self):
        t = {"w": jnp.ones(2)}
        with tempfile.TemporaryDirectory() as d:
            for s in range(5):
                save_checkpoint(d, t, s, keep=2)
            steps = sorted(int(n.split("_")[1]) for n in os.listdir(d)
                           if n.startswith("step_"))
            assert steps == [3, 4]

    def test_latest_step_ignores_partial(self):
        t = {"w": jnp.ones(2)}
        with tempfile.TemporaryDirectory() as d:
            save_checkpoint(d, t, 3)
            os.makedirs(os.path.join(d, "step_0000000009"))  # no manifest
            assert latest_step(d) == 3

    def test_async_checkpointer(self):
        t = {"w": jnp.ones(2)}
        with tempfile.TemporaryDirectory() as d:
            ck = AsyncCheckpointer(d, keep=2)
            ck.save(t, 1)
            ck.save(t, 2)       # waits for 1 internally
            ck.wait()
            assert latest_step(d) == 2

    def test_missing_key_raises(self):
        with tempfile.TemporaryDirectory() as d:
            save_checkpoint(d, {"w": jnp.ones(2)}, 1)
            with pytest.raises(KeyError):
                restore_checkpoint(d, {"other": jnp.ones(2)})

    def test_missing_key_nonstrict_keeps_like_leaf(self):
        """strict=False: keys absent from the checkpoint keep the
        ``like`` value — how the Trainer resumes a pre-dp-path
        checkpoint with zero-initialised error-feedback state."""
        with tempfile.TemporaryDirectory() as d:
            save_checkpoint(d, {"w": jnp.ones(2)}, 1)
            like = {"w": jnp.zeros(2), "err": jnp.full(3, 7.0)}
            restored, step = restore_checkpoint(d, like, strict=False)
            np.testing.assert_array_equal(np.asarray(restored["w"]),
                                          [1, 1])
            np.testing.assert_array_equal(np.asarray(restored["err"]),
                                          [7, 7, 7])

    def test_shape_mismatch_raises(self):
        """A re-mesh restore must never silently re-lay-out a
        wrong-shaped leaf (e.g. grad_accum_shards changed between
        runs)."""
        with tempfile.TemporaryDirectory() as d:
            save_checkpoint(d, {"e": jnp.zeros((8, 4))}, 1)
            with pytest.raises(ValueError, match="shape"):
                restore_checkpoint(d, {"e": jnp.zeros((4, 4))})

    def test_save_while_previous_save_in_flight(self, monkeypatch):
        """save() must drain the in-flight write before starting the
        next one — interleaved async saves land in order and GC sees
        every step."""
        import time as _time

        from repro.ckpt import checkpoint as ck_mod

        orig = ck_mod.save_checkpoint
        calls = []

        def slow_save(directory, tree, step, **kw):
            calls.append(("start", step))
            if step == 1:
                _time.sleep(0.3)
            out = orig(directory, tree, step, **kw)
            calls.append(("end", step))
            return out

        monkeypatch.setattr(ck_mod, "save_checkpoint", slow_save)
        t = {"w": jnp.ones(2)}
        with tempfile.TemporaryDirectory() as d:
            ck = AsyncCheckpointer(d, keep=3)
            ck.save(t, 1)
            ck.save(t, 2)               # must block on 1 first
            ck.wait()
            assert latest_step(d) == 2
            assert calls == [("start", 1), ("end", 1),
                             ("start", 2), ("end", 2)]

    def test_wait_after_failure_raises_once_then_recovers(self, tmp_path):
        """A failed async write surfaces on the next wait() exactly
        once; the checkpointer is reusable afterwards."""
        blocker = tmp_path / "not_a_dir"
        blocker.write_text("file where the ckpt dir should be")
        ck = AsyncCheckpointer(str(blocker), keep=2)
        ck.save({"w": jnp.ones(2)}, 1)
        with pytest.raises(OSError):
            ck.wait()
        ck.wait()                       # error was consumed — no raise
        # a save() after a failure also surfaces the error exactly once
        ck.save({"w": jnp.ones(2)}, 2)
        with pytest.raises(OSError):
            ck.wait()
        good = tmp_path / "ckpt"
        ck2 = AsyncCheckpointer(str(good), keep=2)
        ck2.save({"w": jnp.ones(2)}, 3)
        ck2.wait()
        assert latest_step(str(good)) == 3

    def test_gc_keep_honoured_under_interleaved_async_saves(self):
        t = {"w": jnp.ones(2)}
        with tempfile.TemporaryDirectory() as d:
            ck = AsyncCheckpointer(d, keep=2)
            for s in range(1, 6):
                ck.save(t, s)           # each drains the previous one
            ck.wait()
            steps = sorted(int(n.split("_")[1]) for n in os.listdir(d)
                           if n.startswith("step_"))
            assert steps == [4, 5]


class TestMetrics:
    def test_rank_of(self):
        scores = jnp.array([[0.1, 0.9, 0.5], [0.7, 0.2, 0.3]])
        np.testing.assert_array_equal(
            np.asarray(rank_of(scores, jnp.array([1, 0]))), [1, 1])
        np.testing.assert_array_equal(
            np.asarray(rank_of(scores, jnp.array([0, 1]))), [3, 3])

    def test_ndcg_formula(self):
        scores = jnp.array([[0.9, 0.5, 0.1]])
        assert float(ndcg_at_k(scores, jnp.array([0]), 10)[0]) == \
            pytest.approx(1.0)
        assert float(ndcg_at_k(scores, jnp.array([1]), 10)[0]) == \
            pytest.approx(1 / np.log2(3))

    def test_hr_cutoff(self):
        scores = jnp.array([[5, 4, 3, 2, 1.0]])
        assert float(hr_at_k(scores, jnp.array([4]), 3)[0]) == 0.0
        assert float(hr_at_k(scores, jnp.array([1]), 3)[0]) == 1.0


class TestData:
    def test_batches_deterministic_in_step(self):
        d = SyntheticSequences(SeqDataConfig(n_users=50, n_items=40,
                                             seq_len=8))
        b1 = d.train_batch(3, 4)
        b2 = d.train_batch(3, 4)
        np.testing.assert_array_equal(b1["seq"], b2["seq"])
        b3 = d.train_batch(4, 4)
        assert not np.array_equal(b1["seq"], b3["seq"])

    def test_leave_one_out_split(self):
        d = SyntheticSequences(SeqDataConfig(n_users=30, n_items=40,
                                             seq_len=8))
        u = 0
        full = d.seqs[u]
        assert d.test_target(u) == full[-1]
        assert d.val_target(u) == full[-2]
        assert len(d.train_seq(u)) == len(full) - 2

    def test_long_tail_knob(self):
        lo = SyntheticSequences(SeqDataConfig(n_users=400, n_items=100,
                                              zipf_a=0.2, seed=1))
        hi = SyntheticSequences(SeqDataConfig(n_users=400, n_items=3000,
                                              zipf_a=1.4, seed=1))
        assert hi.long_tail_share() > lo.long_tail_share() + 0.2

    def test_clicks_have_signal(self):
        data = SyntheticClicks(ClickDataConfig(n_dense=4,
                                               vocab_sizes=(50, 50)))
        b = data.batch(0, 4096)
        # planted logit should separate labels
        assert 0.2 < b["label"].mean() < 0.8

    def test_neighbor_sampler_shapes(self):
        g = make_graph(GraphConfig(n_nodes=200, n_edges=1000))
        indptr, nbrs = to_csr(g["senders"], g["receivers"], 200)
        rng = np.random.default_rng(0)
        seeds = rng.choice(200, 16, replace=False)
        send, recv, nodes = sample_block(indptr, nbrs, seeds, [5, 3], rng)
        assert recv.max() < len(nodes)
        batch = pad_block(send, recv, nodes, g, max_nodes=512,
                          max_edges=512, seeds_n=16)
        assert batch["features"].shape == (512, 64)
        assert batch["node_mask"].sum() == 16
        # sampled edges point at real neighbours
        for s, r in list(zip(send, recv))[:20]:
            src, dst = nodes[s], nodes[r]
            row = nbrs[indptr[dst]:indptr[dst + 1]]
            assert src in row

    def test_dien_batch_layout(self):
        d = SyntheticSequences(SeqDataConfig(n_users=50, n_items=40,
                                             seq_len=8))
        b = dien_batch(d, 0, 8, 8)
        assert b["hist"].shape == (8, 8) and b["label"].shape == (8,)

    def test_twotower_batch_min_length_corpus(self):
        """Raw sequences of exactly 3 items leave train sequences of
        length 1 — the cut draw used to crash (rng.integers(1, 1))."""
        d = SyntheticSequences(SeqDataConfig(
            n_users=40, n_items=20, n_clusters=1, min_len=3, max_len=3,
            seq_len=8))
        assert d.n_users_eff > 0
        assert all(len(d.train_seq(u)) == 1
                   for u in range(d.n_users_eff))
        b = d.twotower_batch(0, 16, 8)
        assert b["user_hist"].shape == (16, 8)
        assert b["pos_item"].min() >= 1          # the lone item
        assert (b["user_hist"] == 0).all()       # empty histories pad
        assert np.isfinite(b["logq"]).all()

    def test_train_batch_negatives_never_collide(self):
        # 2-item catalogue: a uniform draw collides half the time, so
        # any surviving collision shows up immediately
        d = SyntheticSequences(SeqDataConfig(
            n_users=50, n_items=2, n_clusters=1, min_len=6, max_len=10,
            seq_len=8))
        b = d.train_batch(0, 16, n_negatives=4)
        lab = b["labels"][..., None]
        neg = b["negatives"]
        assert ((neg != lab) | (lab == 0)).all(), \
            "negative collided with its positive label"
        assert neg.min() >= 1 and neg.max() <= 2
        # and on a bigger catalogue the negatives stay in range
        d2 = SyntheticSequences(SeqDataConfig(n_users=50, n_items=40,
                                              seq_len=8))
        b2 = d2.train_batch(1, 8, n_negatives=3)
        assert b2["negatives"].min() >= 1
        assert b2["negatives"].max() <= 40
        assert ((b2["negatives"] != b2["labels"][..., None])
                | (b2["labels"][..., None] == 0)).all()


class TestOptimizerWeightDecay:
    """weight_decay must apply (decoupled) for EVERY optimizer kind —
    sgd and adam silently ignored it, so sweeps setting it trained
    undecayed while reporting the decayed config."""

    def _step(self, kind, wd, p0=2.0, g0=0.5, lr=0.1):
        from repro.train.optimizer import apply_updates, init_opt_state
        cfg = OptConfig(kind=kind, lr=lr, weight_decay=wd,
                        clip_norm=None)
        values = {"w": jnp.full((3,), p0, jnp.float32)}
        grads = {"w": jnp.full((3,), g0, jnp.float32)}
        new_v, new_s, _ = apply_updates(cfg, init_opt_state(values),
                                        values, grads)
        return float(np.asarray(new_v["w"])[0]), new_s

    def test_sgd_hand_computed(self):
        lr, wd, p0, g0 = 0.1, 0.01, 2.0, 0.5
        got, _ = self._step("sgd", wd, p0, g0, lr)
        assert got == pytest.approx(p0 - lr * (g0 + wd * p0), abs=1e-7)
        got0, _ = self._step("sgd", 0.0, p0, g0, lr)
        assert got0 == pytest.approx(p0 - lr * g0, abs=1e-7)
        assert got < got0                   # decay really pulled down

    def _adam_update(self, g0, b1=0.9, b2=0.999, eps=1e-8):
        # first step: m=(1-b1)g, v=(1-b2)g^2, both bias-corrected -> g
        m_hat = (1 - b1) * g0 / (1 - b1)
        v_hat = (1 - b2) * g0 ** 2 / (1 - b2)
        return m_hat / (np.sqrt(v_hat) + eps)

    def test_adam_hand_computed(self):
        lr, wd, p0, g0 = 0.1, 0.01, 2.0, 0.5
        upd = self._adam_update(g0)
        got, state = self._step("adam", wd, p0, g0, lr)
        assert got == pytest.approx(p0 - lr * (upd + wd * p0), rel=1e-6)
        # moments really accumulated (adam != sgd internally)
        assert float(np.asarray(state["m"]["w"])[0]) == \
            pytest.approx(0.1 * g0, rel=1e-5)

    def test_adamw_hand_computed_and_unchanged(self):
        lr, wd, p0, g0 = 0.1, 0.01, 2.0, 0.5
        upd = self._adam_update(g0)
        got, _ = self._step("adamw", wd, p0, g0, lr)
        assert got == pytest.approx(p0 - lr * (upd + wd * p0), rel=1e-6)

    def test_decay_is_decoupled_from_clip(self):
        """The decay term scales with lr but NOT with the grad-clip
        scale — clipping a huge gradient must not also shrink the
        decay (the decoupled formulation)."""
        from repro.train.optimizer import apply_updates, init_opt_state
        p0, wd, lr = 2.0, 0.1, 0.1
        values = {"w": jnp.full((1,), p0, jnp.float32)}
        grads = {"w": jnp.full((1,), 1e4, jnp.float32)}   # clipped hard
        cfg = OptConfig(kind="sgd", lr=lr, weight_decay=wd,
                        clip_norm=1.0)
        new_v, _, stats = apply_updates(cfg, init_opt_state(values),
                                        values, grads)
        clipped_g = 1.0                     # norm-1 after clipping
        assert float(np.asarray(new_v["w"])[0]) == pytest.approx(
            p0 - lr * (clipped_g + wd * p0), rel=1e-5)


class TestStepScopes:
    """The training step names its layers for a profile: each scope is
    in the compiled step's op metadata, its gradient under
    ``transpose(``."""

    @pytest.mark.parametrize("arch, emb, scopes", [
        ("sasrec", EmbeddingConfig(0, 0, kind="jpq", m=4, b=8),
         ("seqrec.encode", "item_emb.logits", "seqrec.loss_head",
          "train.optimizer")),
        ("gru4rec", None, ("seqrec.encode",)),
    ])
    def test_compiled_step_carries_the_scopes(self, arch, emb, scopes):
        import re

        from repro.nn import module as nn
        from repro.train.optimizer import init_opt_state
        cfg = SeqRecConfig(arch=arch, n_items=30, max_len=8, d_model=16,
                           n_layers=1, n_heads=2, d_ff=32, embedding=emb)
        model = SeqRecModel(cfg)
        tr = Trainer(model, OptConfig(), TrainConfig(batch_size=4),
                     data_fn=None)
        meta = model.init_params(jax.random.PRNGKey(0))
        values = nn.values(meta)
        seq = jnp.ones((4, 8), jnp.int32)
        text = jax.jit(tr._build_step(meta)).lower(
            values, init_opt_state(values), {"seq": seq, "labels": seq},
            jax.random.PRNGKey(1)).compile().as_text()
        names = re.findall(r'op_name="([^"]*)"', text)
        for scope in scopes:
            assert any(scope in n and "transpose(" not in n
                       for n in names), scope
        if "item_emb.logits" in scopes:
            assert any("transpose(jvp(item_emb.logits))" in n
                       for n in names)


class TestTrainerIntegration:
    def test_preemption_saves_and_resumes(self):
        cfg = SeqRecConfig(arch="gru4rec", n_items=30, max_len=8,
                           d_model=16, n_layers=1)
        model = SeqRecModel(cfg)
        data = SyntheticSequences(SeqDataConfig(n_users=40, n_items=30,
                                                seq_len=8))
        with tempfile.TemporaryDirectory() as td:
            tr = Trainer(model, OptConfig(lr=1e-2),
                         TrainConfig(steps=10, batch_size=8, ckpt_dir=td,
                                     ckpt_every=5, log_every=100,
                                     eval_every=0),
                         data_fn=lambda s: data.train_batch(s, 8))
            tr._preempted = False
            params, _ = tr.run()
            assert latest_step(td) == 10
            tr2 = Trainer(model, OptConfig(lr=1e-2),
                          TrainConfig(steps=12, batch_size=8, ckpt_dir=td,
                                      ckpt_every=0, log_every=1,
                                      eval_every=0),
                          data_fn=lambda s: data.train_batch(s, 8))
            _, hist = tr2.run()
            assert hist[0]["step"] == 10       # resumed, not restarted

    def test_preemption_checkpoint_stamped_at_actual_step(self):
        """A SIGTERM-preemption break must stamp the checkpoint at the
        step actually reached — stamping cfg.steps made resume restore
        AT cfg.steps and skip the remaining training entirely."""
        cfg = SeqRecConfig(arch="gru4rec", n_items=30, max_len=8,
                           d_model=16, n_layers=1)
        model = SeqRecModel(cfg)
        data = SyntheticSequences(SeqDataConfig(n_users=40, n_items=30,
                                                seq_len=8))
        with tempfile.TemporaryDirectory() as td:
            box = {}

            def data_fn(s):
                if s == 3:                 # "SIGTERM" mid-run
                    box["tr"]._preempted = True
                return data.train_batch(s, 8)

            tr = Trainer(model, OptConfig(lr=1e-2),
                         TrainConfig(steps=10, batch_size=8, ckpt_dir=td,
                                     ckpt_every=0, log_every=100,
                                     eval_every=0),
                         data_fn=data_fn)
            box["tr"] = tr
            tr.run()
            # preempted after finishing step 3 -> checkpoint at step 4,
            # and no trailing save re-stamps it at cfg.steps
            assert latest_step(td) == 4
            tr2 = Trainer(model, OptConfig(lr=1e-2),
                          TrainConfig(steps=10, batch_size=8,
                                      ckpt_dir=td, ckpt_every=0,
                                      log_every=1, eval_every=0),
                          data_fn=lambda s: data.train_batch(s, 8))
            _, hist = tr2.run()
            assert hist[0]["step"] == 4    # resumed where it stopped
            assert latest_step(td) == 10   # ... and finished the run

    def test_microbatch_rng_folds_and_metrics_flow(self):
        """Each accumulation slice must see a DIFFERENT rng (identical
        dropout masks across microbatches otherwise), grads must equal
        the mean of per-slice grads under those rngs, and the full
        metrics dict (not just loss) must survive accumulation."""
        from repro.nn import module as nn
        from repro.nn.module import P
        from repro.train.optimizer import init_opt_state

        class _Probe:
            def init_params(self, rng):
                return {"w": P(jnp.zeros(()), ())}

            def train_loss(self, params, batch, rng):
                u = jax.random.uniform(rng, ())
                loss = params["w"].value * u + 0.0 * jnp.mean(batch["x"])
                return loss, {"loss": loss, "probe": u}

        nm = 4
        tr = Trainer(_Probe(), OptConfig(kind="sgd", lr=1.0,
                                         clip_norm=None),
                     TrainConfig(steps=1, batch_size=8, microbatches=nm),
                     data_fn=None)
        meta = tr.model.init_params(jax.random.PRNGKey(0))
        step_fn = jax.jit(tr._build_step(meta))
        values = nn.values(meta)
        rng = jax.random.PRNGKey(5)
        new_values, _, mets = step_fn(values, init_opt_state(values),
                                      {"x": jnp.zeros((8,))}, rng)
        per_slice = [float(jax.random.uniform(
            jax.random.fold_in(rng, i), ())) for i in range(nm)]
        shared = float(jax.random.uniform(rng, ()))
        # dropout-style rng differs per slice...
        assert float(mets["probe"]) == pytest.approx(
            np.mean(per_slice), rel=1e-6)
        assert abs(float(mets["probe"]) - shared) > 1e-3
        # ...grads are the mean of per-slice grads (d(w*u)/dw = u)...
        assert float(new_values["w"]) == pytest.approx(
            -np.mean(per_slice), rel=1e-6)
        # ...and nothing beyond "loss" is dropped on the floor
        assert "probe" in mets and "grad_norm" in mets and "lr" in mets

    def test_grad_compression_requires_mesh(self):
        cfg = SeqRecConfig(arch="gru4rec", n_items=30, max_len=8,
                           d_model=16, n_layers=1)
        with pytest.raises(ValueError, match="mesh"):
            Trainer(SeqRecModel(cfg), OptConfig(),
                    TrainConfig(grad_compression="int8"),
                    data_fn=None)

    def test_grad_compression_rejects_microbatches(self):
        import jax as _jax
        cfg = SeqRecConfig(arch="gru4rec", n_items=30, max_len=8,
                           d_model=16, n_layers=1)
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((1, 1), ("data", "model"))
        with pytest.raises(ValueError, match="microbatches"):
            Trainer(SeqRecModel(cfg), OptConfig(),
                    TrainConfig(grad_compression="bf16", microbatches=2),
                    data_fn=None, mesh=mesh)

    def test_unknown_grad_compression_rejected(self):
        cfg = SeqRecConfig(arch="gru4rec", n_items=30, max_len=8,
                           d_model=16, n_layers=1)
        with pytest.raises(ValueError, match="unknown"):
            Trainer(SeqRecModel(cfg), OptConfig(),
                    TrainConfig(grad_compression="fp4"), data_fn=None)

    def test_early_stop_state_survives_preempt_resume(self):
        """Early-stop best/stale must checkpoint next to "opt": a
        resumed run that re-armed the full patience window trained past
        where the uninterrupted run stopped, breaking run-equivalence.
        Eval lands on odd steps (eval_every=2) and the preemption on an
        even one, so both runs see the identical metric sequence."""
        cfg = SeqRecConfig(arch="gru4rec", n_items=30, max_len=8,
                           d_model=16, n_layers=1)
        data = SyntheticSequences(SeqDataConfig(n_users=40, n_items=30,
                                                seq_len=8))
        # step -> metric: peak at the first eval, then decline; with
        # patience=2 the run must stop after the step-5 eval (stale=2)
        metric_by_step = {1: 0.9, 3: 0.8, 5: 0.7, 7: 0.6, 9: 0.5}

        def make_run(td, preempt_at=None):
            box = {}

            def data_fn(s):
                box["step"] = s
                if preempt_at is not None and s == preempt_at:
                    box["tr"]._preempted = True
                return data.train_batch(s, 8)

            def eval_fn(params):
                return {"metric": metric_by_step[box["step"]]}

            tr = Trainer(SeqRecModel(cfg), OptConfig(lr=1e-2),
                         TrainConfig(steps=20, batch_size=8,
                                     ckpt_dir=td, ckpt_every=0,
                                     log_every=100, eval_every=2,
                                     early_stop_patience=2),
                         data_fn=data_fn, eval_fn=eval_fn)
            box["tr"] = tr
            return tr

        with tempfile.TemporaryDirectory() as d_ref, \
                tempfile.TemporaryDirectory() as d_int:
            ref = make_run(d_ref)
            p_ref, _ = ref.run()
            assert ref.done_step == 6          # stopped by patience

            intr = make_run(d_int, preempt_at=2)
            intr.run()
            assert intr.done_step == 3         # really preempted
            res = make_run(d_int)
            p_res, _ = res.run()
            # same stopping step as the uninterrupted run — the best
            # metric (0.9, seen before the preemption) must have been
            # restored, not re-armed to -inf
            assert res.done_step == ref.done_step
            va = [np.asarray(p.value) for p in jax.tree.leaves(
                p_ref, is_leaf=lambda x: hasattr(x, "value"))]
            vb = [np.asarray(p.value) for p in jax.tree.leaves(
                p_res, is_leaf=lambda x: hasattr(x, "value"))]
            assert all(np.array_equal(a, b) for a, b in zip(va, vb))

    def test_step_times_reset_between_runs(self):
        """The slow-step watchdog's per-step samples must not leak
        from a previous run() on the same Trainer — a second run's
        medians would be computed against a stale mesh/compile
        baseline."""
        cfg = SeqRecConfig(arch="gru4rec", n_items=30, max_len=8,
                           d_model=16, n_layers=1)
        data = SyntheticSequences(SeqDataConfig(n_users=40, n_items=30,
                                                seq_len=8))
        tr = Trainer(SeqRecModel(cfg), OptConfig(lr=1e-2),
                     TrainConfig(steps=5, batch_size=8, log_every=100,
                                 eval_every=0),
                     data_fn=lambda s: data.train_batch(s, 8))
        tr.run()
        assert len(tr._step_times) == 5
        tr.run()
        assert len(tr._step_times) == 5        # reset, not 10

    def test_watchdog_times_the_device_work_not_the_enqueue(self):
        """A step whose device work is slow, while its dispatch returns
        at once, is flagged as a straggler: the loop times each step to
        its completion, not to the return of its dispatch.  The slow
        work is a loop of matrix products inside the jitted step, taken
        only where the batch says so; JAX dispatches it asynchronously
        (a host callback would run inline at dispatch instead)."""
        cfg = SeqRecConfig(arch="gru4rec", n_items=30, max_len=8,
                           d_model=16, n_layers=1)
        data = SyntheticSequences(SeqDataConfig(n_users=40, n_items=30,
                                                seq_len=8))
        slow_step, steps = 25, 30

        def heavy():
            x = jnp.full((512, 512), 1e-3)
            return jax.lax.fori_loop(
                0, 300, lambda i, y: jnp.tanh(y @ y), x)[0, 0]

        class SlowAt(SeqRecModel):
            def train_loss(self, p, batch, rng=None):
                loss, mets = super().train_loss(p, batch, rng)
                work = jax.lax.cond(batch["slow"] > 0, heavy,
                                    lambda: jnp.zeros(()))
                return loss, {**mets, "slow_work": work}

        def data_fn(s):
            b = data.train_batch(s, 8)
            b["slow"] = np.int32(s == slow_step)
            return b

        tr = Trainer(SlowAt(cfg), OptConfig(lr=1e-2),
                     TrainConfig(steps=steps, batch_size=8, log_every=1,
                                 eval_every=0),
                     data_fn=data_fn)
        _, hist = tr.run()
        assert len(tr._step_times) == steps
        assert slow_step in [h["step"] for h in hist
                             if "straggler_sec" in h]
        sec = {h["step"]: h["sec"] for h in hist if "sec" in h}
        assert sorted(sec) == list(range(steps))

    def test_microbatch_grad_accumulation_matches(self):
        """2 microbatches ~= full batch (same data, mean loss)."""
        cfg = SeqRecConfig(arch="gru4rec", n_items=30, max_len=8,
                           d_model=16, n_layers=1)
        model = SeqRecModel(cfg)
        data = SyntheticSequences(SeqDataConfig(n_users=40, n_items=30,
                                                seq_len=8))
        histories = []
        for nm in (1, 2):
            tr = Trainer(model, OptConfig(kind="sgd", lr=1e-2,
                                          clip_norm=None),
                         TrainConfig(steps=3, batch_size=8, log_every=1,
                                     eval_every=0, microbatches=nm),
                         data_fn=lambda s: data.train_batch(s, 8))
            _, hist = tr.run()
            histories.append([h["loss"] for h in hist if "loss" in h])
        # microbatch normalisation differs slightly when pad counts differ
        np.testing.assert_allclose(histories[0], histories[1], rtol=5e-2)

"""Closed-loop training cell: the program's training step, driven step
after step for the window.

Set-up builds one object, the step that ``Trainer`` builds from its
``TrainSpec`` and jits as ``Trainer.run`` does, with its state; drives
it through its first three steps with the window's own call and feed;
and hands the same object to the window.  The rows of every batch are
distinct users of the seeded corpus, in an order drawn from the seed.

Correctness: the plain reference repeats the first three steps from
the same initial values and batches at float32 ``HIGHEST`` and is
compared by three numbers (``check``).
"""
from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

from chip import harness, weights
from chip.traffic import sessions

FIRST_STEPS = 3


def _named_leaves(ref, tree) -> dict:
    """Float leaves of a value tree as {path: array}, codes left out."""
    out = {}
    for path, x in jax.tree_util.tree_flatten_with_path(
            ref.float_leaves(tree))[0]:
        out[jax.tree_util.keystr(path)] = np.asarray(x, np.float64)
    return out


def leaf_gap(prog: dict, want: dict, keep=None) -> float:
    """The worst leaf's gap between the program's norm and the
    reference's, over the larger of that leaf's reference norm and the
    median leaf's."""
    norms = {k: float(np.linalg.norm(v)) for k, v in want.items()}
    med = float(np.median(list(norms.values())))
    gaps = [abs(float(np.linalg.norm(prog[k])) - norms[k])
            / max(norms[k], med, 1e-30)
            for k in want if keep is None or k in keep]
    return max(gaps)


class Cell:
    """One training cell.  ``fault`` plants a fault in the timed path
    for the harness's own tests and the limits' readings:
    "unchanged" (the step returns its state unchanged) or "half_batch"
    (half of each batch's rows lose their labels, so the mean is taken
    over the rest)."""

    def __init__(self, *, config: dict, traffic: dict, seed: int, spans,
                 fault=None):
        self.config = config
        self.traffic = traffic
        self.seed = int(seed)
        self.spans = spans
        self.fault = fault
        self.ref = harness.reference(config)
        self.batch = int(traffic["batch"])
        self.failed = 0
        self.steps = 0

    # ---------------------------------------------------------- set-up
    def setup(self) -> None:
        from repro.train.loop import TrainConfig, Trainer
        from repro.train.optimizer import OptConfig, init_opt_state
        from repro.train.spec import TrainSpec
        cfg, tr = self.config, self.traffic
        with self.spans.span("bench.setup_data"):
            _, items, lengths, codes = weights.corpus_and_codes(
                cfg, self.seed)
        # the program's training protocol: the last two items of a
        # session are held out; item t predicts item t + 1
        n = lengths - 2
        L = cfg["max_len"]
        self.seq = sessions.left_pad(items, n - 1, L)
        self.labels = sessions.left_pad(items[:, 1:], n - 1, L)
        self.order = np.random.default_rng([self.seed, 4]).permutation(
            items.shape[0])
        with self.spans.span("bench.setup_weights"):
            values = self.ref.make_values(cfg, codes, self.seed)
            model, params = weights.program_model(cfg, values)
        self.opt_cfg = OptConfig(lr=tr["lr"])
        trainer = Trainer(model, self.opt_cfg,
                          TrainConfig(batch_size=self.batch,
                                      seed=self.seed),
                          data_fn=None, spec=TrainSpec())
        self.step_fn = jax.jit(trainer._build_step(params),
                               donate_argnums=(0, 1))
        if self.fault == "unchanged":
            inner = self.step_fn
            self.step_fn = lambda v, o, b, r: (v, o, inner(
                jax.tree.map(jnp.copy, v), jax.tree.map(jnp.copy, o),
                b, r)[2])
        self.key = jax.random.PRNGKey(self.seed % 2 ** 32)
        self.v0 = jax.tree.map(np.asarray, values)
        self.values = values
        self.opt = init_opt_state(values)
        self.losses = []
        for i in range(FIRST_STEPS):
            self.losses.append(float(self._step(i)["loss"]))
            if i == 0:
                self.g1 = jax.tree.map(
                    lambda m: np.asarray(m) / (1.0 - self.opt_cfg.b1),
                    self.opt["m"])
        self.v3 = jax.tree.map(np.asarray, self.values)
        self.failed += sum(not np.isfinite(x) for x in self.losses)

    def clean_rows(self, i: int):
        """Batch ``i``: distinct users in the seeded order."""
        idx = self.order[(i * self.batch + np.arange(self.batch))
                         % self.order.size]
        return self.seq[idx], self.labels[idx]

    def rows(self, i: int):
        """Batch ``i`` as the timed path gets it."""
        seq, labels = self.clean_rows(i)
        if self.fault == "half_batch":
            labels = labels.copy()
            labels[self.batch // 2:] = 0
        return seq, labels

    def _step(self, i: int):
        with self.spans.span("bench.data_batch"):
            seq, labels = self.rows(i)
            batch = {"seq": jnp.asarray(seq), "labels": jnp.asarray(labels)}
        with self.spans.span("bench.step_dispatch"):
            self.values, self.opt, mets = self.step_fn(
                self.values, self.opt, batch, jax.random.fold_in(self.key, i))
        return mets

    # ---------------------------------------------------------- window
    def window(self, seconds: float, clock) -> tuple:
        """Steps until ``seconds`` have passed; returns the window's
        (start, end) on ``clock``, its end once the last step is done."""
        i = FIRST_STEPS
        pending = None
        t0 = clock()
        while clock() - t0 < seconds:
            mets = self._step(i)
            i += 1
            if pending is not None:
                with self.spans.span("bench.loss_readback"):
                    self.failed += not np.isfinite(float(pending["loss"]))
            pending = mets
        with self.spans.span("bench.loss_readback"):
            self.failed += not np.isfinite(float(pending["loss"]))
        t1 = clock()
        self.steps = i - FIRST_STEPS
        return t0, t1

    def end_to_end(self, t0: float, t1: float) -> dict:
        return {"train_seq_per_s": self.steps * self.batch / (t1 - t0)}

    def counters(self) -> dict:
        return {"steps": self.steps, "batch": self.batch}

    def attempted(self) -> int:
        return self.steps

    def free(self) -> None:
        del self.values, self.opt, self.step_fn

    # ----------------------------------------------------- correctness
    def reference(self, mode: str = "f32"):
        """The reference's first three steps from the benchmark's
        initial values: (losses, first clipped gradient, change)."""
        o, ref = self.opt_cfg, self.ref
        v = jax.tree.map(jnp.asarray, self.v0)
        m = jax.tree.map(jnp.zeros_like, ref.float_leaves(v))
        s = jax.tree.map(jnp.zeros_like, ref.float_leaves(v))
        losses, g1 = [], None
        for i in range(FIRST_STEPS):
            seq, labels = self.clean_rows(i)
            loss, g = ref.loss_and_grad(v, jnp.asarray(seq),
                                        jnp.asarray(labels),
                                        self.config["n_heads"], mode)
            v, m, s, gc = ref.adam_step(v, g, m, s, i + 1, lr=o.lr, b1=o.b1,
                                        b2=o.b2, eps=o.eps,
                                        clip_norm=o.clip_norm)
            losses.append(float(loss))
            if i == 0:
                g1 = _named_leaves(ref, ref.with_float_leaves(v, gc))
        v0 = _named_leaves(self.ref, self.v0)
        change = {k: x - v0[k] for k, x in _named_leaves(ref, v).items()}
        return losses, g1, change

    def program_readings(self):
        g1 = _named_leaves(self.ref, self.g1)
        v0 = _named_leaves(self.ref, self.v0)
        v3 = _named_leaves(self.ref, self.v3)
        change = {k: x - v0[k] for k, x in v3.items()}
        return self.losses, g1, change

    @staticmethod
    def compare(prog, want) -> dict:
        """The three numbers compared: the worst step's relative loss
        gap; the first gradient's worst-leaf norm gap; the worst-leaf
        norm gap of the change after three steps, over the leaves whose
        reference gradient is at least a thousandth of the median
        leaf's (the others move under Adam by round-off alone)."""
        pl, pg, pc = prog
        rl, rg, rc = want
        loss_gap = max(abs(a - b) / abs(b) for a, b in zip(pl, rl))
        gn = {k: float(np.linalg.norm(v)) for k, v in rg.items()}
        med = float(np.median(list(gn.values())))
        keep = {k for k, n in gn.items() if n >= 1e-3 * med}
        return {"loss_gap": loss_gap, "grad_gap": leaf_gap(pg, rg),
                "change_gap": leaf_gap(pc, rc, keep)}

    def check(self) -> dict:
        return self.compare(self.program_readings(), self.reference("f32"))

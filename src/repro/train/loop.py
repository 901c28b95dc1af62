"""Training loop: jit'd step, microbatching, early stopping, checkpoints,
preemption handling, step-time watchdog (straggler logging).

Works single-host (CPU validation runs) and under a mesh: pass ``mesh``
and the loop resolves parameter/optimizer shardings from the logical
axis rules, jits with those in/out shardings, and constrains batches to
the data axes.  With ``TrainConfig.grad_compression`` /
``grad_accum_shards`` the mesh step instead routes through the elastic
compressed-gradient exchange (``repro.dist.compression``): bf16/int8
payloads with error feedback carried — and checkpointed — next to the
optimizer state, bitwise deterministic across mesh sizes so a
preempted run resumes on a smaller mesh bit-identically
(docs/sharding.md).  This same class is what launch/train.py drives.

All of that policy now lives in one value object: the Trainer derives
(or is handed) a ``repro.train.spec.TrainSpec`` and builds its step
through the step-builder registry (``spec.build_train_step``), the
legacy ``TrainConfig``/``OptConfig`` knobs surviving as a
``spec_for`` shim.  Checkpoints are stamped with the spec's layout
fingerprint so restore verifies compatibility up front instead of
shape-guessing, and the history rows the loop appends are checked
against ``repro.train.metrics.HISTORY_SCHEMA`` at the end of ``run``.
"""
from __future__ import annotations

import dataclasses
import signal
import time
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import dist
from repro.ckpt import (AsyncCheckpointer, checkpoint_metadata,
                        latest_step, restore_checkpoint)
from repro.dist import compression
from repro.nn import module as nn
from repro.train import spec as spec_mod
from repro.train.metrics import validate_history
from repro.train.optimizer import OptConfig, apply_updates, init_opt_state
from repro.train.spec import TrainSpec


@dataclasses.dataclass
class TrainConfig:
    steps: int = 1000
    batch_size: int = 64
    log_every: int = 50
    eval_every: int = 200
    ckpt_every: int = 200
    ckpt_dir: Optional[str] = None
    keep_ckpts: int = 3
    early_stop_patience: int = 0       # 0 = off; in eval rounds
    microbatches: int = 1              # gradient accumulation
    watchdog_factor: float = 3.0       # flag steps slower than f * median
    seed: int = 0
    # --- elastic compressed-gradient exchange (docs/sharding.md) ---
    # None inherits OptConfig.grad_compression; setting either this to
    # "none"/"bf16"/"int8" explicitly, or grad_accum_shards, routes the
    # mesh step through dist.compression.make_elastic_dp_step: the batch
    # is cut into grad_accum_shards fixed virtual shards (default: the
    # mesh's data-parallel degree), payloads are exchanged compressed
    # with per-shard error feedback, and the resulting step is bitwise
    # deterministic across mesh sizes whose dp degree divides the shard
    # count — the property elastic restore (SIGTERM -> resume on a
    # smaller mesh) relies on.  The default (None, method "none") keeps
    # the legacy fp32 jit-sharded step.
    grad_compression: Optional[str] = None
    grad_accum_shards: Optional[int] = None
    # FSDP composition of the elastic exchange: each device owns a 1/D
    # row-slice of params + Adam moments (leaves whose leading dim is
    # divisible by the virtual-shard count; everything else stays
    # replicated), parameters are all-gathered once per step, and the
    # per-round payload collective becomes an ordered reduce-scatter —
    # `payload` wire bytes per device per round instead of V x payload.
    # Implies the dp path; preserves the bitwise-elastic contract.
    fsdp: bool = False
    # Host round schedule for the elastic collect rounds — one of
    # repro.train.spec.OVERLAP_MODES ("none" serial oracle,
    # "dispatch" double-buffered rounds, "backward" backward-of-round
    # r+1 overlapping exchange-of-round r); legacy bools accepted.
    # Wall-clock only: every mode is bitwise identical, so it is NOT
    # part of the checkpoint layout.  None = the default "dispatch".
    overlap: Any = None


class Trainer:
    def __init__(self, model, opt_cfg: OptConfig, train_cfg: TrainConfig,
                 data_fn: Callable[[int], dict],
                 eval_fn: Optional[Callable[[Any], dict]] = None,
                 mesh=None, rules=None,
                 spec: Optional[TrainSpec] = None):
        self.model = model
        self.opt_cfg = opt_cfg
        self.cfg = train_cfg
        self.data_fn = data_fn
        self.eval_fn = eval_fn
        self.mesh = mesh
        self.rules = rules
        self._preempted = False
        self._step_times: list = []
        self.history: list = []
        self.done_step = 0
        self.err_state = None              # error feedback (dp path)
        # the legacy TrainConfig/OptConfig knobs normalise to a
        # TrainSpec (hash-equal to passing the spec directly; a
        # conflicting duplicate grad_compression raises inside
        # spec_for).  An explicit spec wins — but only over *default*
        # legacy knobs: an explicit spec AND a non-default knob
        # disagreeing is ambiguous and raises.
        derived = spec_mod.spec_for(
            grad_compression=train_cfg.grad_compression,
            opt_grad_compression=opt_cfg.grad_compression,
            grad_accum_shards=train_cfg.grad_accum_shards,
            fsdp=train_cfg.fsdp,
            overlap=train_cfg.overlap,
            microbatches=train_cfg.microbatches)
        if spec is None:
            spec = derived
        elif derived != TrainSpec() and derived != spec:
            raise ValueError(
                f"Trainer got an explicit TrainSpec {spec} AND "
                f"conflicting legacy TrainConfig/OptConfig knobs "
                f"(which resolve to {derived}); set the policy in one "
                f"place")
        self.spec = spec
        self._dp_method = spec.compression
        self._fsdp = spec.fsdp
        self._use_dp = spec.elastic
        if self._use_dp and mesh is None:
            raise ValueError(
                "grad_compression / grad_accum_shards / fsdp "
                "require a mesh")
        self._accum = (spec.resolve_accum(mesh)
                       if self._use_dp else None)

    # ----------------------------------------------------------- setup
    def _install_sigterm(self):
        def _handler(signum, frame):
            self._preempted = True
        try:
            signal.signal(signal.SIGTERM, _handler)
        except ValueError:
            pass                                   # non-main thread

    def _loss_and_apply(self, params_meta):
        """The StepContext ingredients shared by every builder: the
        model loss closed over the param metadata, and the optimizer
        apply hook (``grad_norm=`` is how the fsdp combine injects the
        bitwise-deterministic global norm)."""
        model, opt_cfg = self.model, self.opt_cfg

        def loss_fn(values, batch, rng):
            params = nn.with_values(params_meta, values)
            loss, mets = model.train_loss(params, batch, rng)
            return loss, mets

        def apply_fn(values, opt_state, grads, grad_norm=None):
            return apply_updates(opt_cfg, opt_state, values, grads,
                                 grad_norm=grad_norm)

        return loss_fn, apply_fn

    def _build_step(self, params_meta):
        """Plain/microbatch step via the step-builder registry —
        ``train_step(values, opt_state, batch, rng)``.  Kept as a
        method (and un-jitted) because callers jit it with their own
        donation/sharding arguments."""
        loss_fn, apply_fn = self._loss_and_apply(params_meta)
        spec = (self.spec if not self.spec.elastic
                else TrainSpec())            # grads-only debugging use
        return spec_mod.build_train_step(
            spec, loss_fn=loss_fn, mesh=None, apply_fn=apply_fn,
            has_aux=True)

    def _build_dp_step(self, params_meta):
        """Elastic-deterministic compressed-exchange step via the
        registry (docs/sharding.md §Gradient compression in the
        Trainer): returns ``step(values, opt_state, err_state, batch,
        rng) -> (new_values, new_opt, new_err, mets)``.  Parameters
        stay replicated on the plain dp path (the exchange ships
        full-leaf payloads); with ``spec.fsdp`` params/moments are
        row-sharded and the exchange reduce-scatters each round's
        payload instead (docs/sharding.md §FSDP-composed exchange).
        Per-virtual-shard rng folds keep dropout masks mesh-invariant
        either way; ``spec.overlap`` picks the host round schedule."""
        loss_fn, apply_fn = self._loss_and_apply(params_meta)
        return spec_mod.build_train_step(
            self.spec, loss_fn=loss_fn, mesh=self.mesh,
            apply_fn=apply_fn, has_aux=True)

    def _payload_metrics(self, values):
        """Static per-step exchange accounting rows (the conformance
        suite cross-checks these against the HLO collective bytes;
        repro.train.spec.payload_metrics documents the fields)."""
        return spec_mod.payload_metrics(self.spec, values, self.mesh)

    # ------------------------------------------------------------- run
    def run(self, rng=None, resume: bool = True):
        cfg = self.cfg
        self._install_sigterm()
        # per-run watchdog baseline: medians from a previous run() on
        # this Trainer are stale (different mesh, compile state, ...)
        self._step_times = []
        # history rows accumulate across run() calls; the end-of-run
        # schema validation (monotonic step etc.) covers THIS run's
        # rows only — a second run restarts the step counter
        hist_start = len(self.history)
        rng = rng if rng is not None else jax.random.PRNGKey(cfg.seed)
        params_meta = self.model.init_params(rng)
        values = nn.values(params_meta)
        opt_state = init_opt_state(values)
        err_state = (compression.zeros_error_state(values, self._accum)
                     if self._use_dp else None)
        if self._fsdp:
            values, opt_state, err_state = self._fsdp_layout(
                values, opt_state, err_state)
        start_step = 0
        best_metric, stale = -np.inf, 0

        ckpt = None
        if cfg.ckpt_dir:
            ckpt = AsyncCheckpointer(cfg.ckpt_dir, keep=cfg.keep_ckpts)
            if resume and latest_step(cfg.ckpt_dir) is not None:
                # the spec's layout fingerprint was stamped into the
                # manifest at save time; verify compatibility BEFORE
                # touching the arrays so a wrong --grad-accum-shards /
                # --fsdp resume fails with the actionable spec error
                # rather than a bare npz shape mismatch (pre-stamp
                # checkpoints carry no fingerprint and restore
                # unchecked)
                stamp = checkpoint_metadata(cfg.ckpt_dir).get(
                    "train_spec")
                spec_mod.check_restore_layout(stamp, self.spec,
                                              self._accum)
                state = {"values": values, "opt": opt_state}
                shardings = None
                if self.mesh is not None:
                    shardings = self._state_shardings(params_meta,
                                                      state)
                state, start_step = restore_checkpoint(
                    cfg.ckpt_dir, state, shardings=shardings)
                values, opt_state = state["values"], state["opt"]
                if self._use_dp:
                    # restored separately with strict=False: params/opt
                    # stay hard-guarded above, while a checkpoint
                    # written before the dp path existed simply has no
                    # "err" keys — the zero-initialised state stands in
                    err_sh = (self._state_shardings(
                        params_meta, {"err": err_state})
                        if self.mesh is not None else None)
                    err_tree, _ = restore_checkpoint(
                        cfg.ckpt_dir, {"err": err_state},
                        step=start_step, shardings=err_sh,
                        strict=False)
                    err_state = err_tree["err"]
                # early-stop state rides next to "opt" (strict=False:
                # absent in older checkpoints).  Without it a resumed
                # run re-armed the full patience window and could train
                # past where the uninterrupted run stopped — breaking
                # run-equivalence.  No shardings: host scalars, and a
                # device_put would truncate the f64 best metric.
                es_tree, _ = restore_checkpoint(
                    cfg.ckpt_dir,
                    {"early_stop": {"best": np.float64(-np.inf),
                                    "stale": np.int64(0)}},
                    step=start_step, strict=False)
                best_metric = float(es_tree["early_stop"]["best"])
                stale = int(es_tree["early_stop"]["stale"])

        if self._use_dp:
            train_step = self._build_dp_step(params_meta)
        else:
            train_step = self._build_step(params_meta)
            if self.mesh is not None:
                shardings = dist.params_shardings(params_meta, self.mesh,
                                                  self.rules)
                opt_sh = _opt_shardings(opt_state, params_meta, self.mesh,
                                        self.rules)
                train_step = jax.jit(
                    train_step, donate_argnums=(0, 1),
                    in_shardings=(shardings, opt_sh, None, None),
                    out_shardings=(shardings, opt_sh, None))
            else:
                train_step = jax.jit(train_step, donate_argnums=(0, 1))

        # the final checkpoint must be stamped with the step actually
        # reached: stamping cfg.steps after a preemption/early-stop
        # break made resume restore AT cfg.steps and skip the remaining
        # training entirely.  done_step tracks reality; last_saved
        # prevents the trailing save from duplicating a periodic or
        # preemption save at the same step.
        done_step, last_saved = start_step, None
        # the dp path runs the model loss inside shard_map where
        # sharding constraints don't apply — no ambient mesh there
        ctx = (dist.use_mesh_rules(self.mesh, self.rules)
               if self.mesh is not None and not self._use_dp
               else _nullctx())
        payload_mets = (self._payload_metrics(values)
                        if self._use_dp else {})

        def _ckpt_state():
            state = {"values": values, "opt": opt_state,
                     "early_stop": {"best": np.float64(best_metric),
                                    "stale": np.int64(stale)}}
            if self._use_dp:
                state["err"] = err_state
            return state

        # every save is stamped with the spec's layout fingerprint —
        # the restore path above is the consumer
        ckpt_meta = {"train_spec": self.spec.layout_stamp(self.mesh)}

        # a step's time runs from the previous step's completion (or
        # its own start, if the host was busy past that) to its own
        # completion.  Dispatch returns before the device is done, so
        # each step is waited for one step behind, with the next one
        # already queued, and before anything that reads its state.
        in_flight = None                   # (step, mets, start)
        t_done = time.perf_counter()

        def settle():
            nonlocal in_flight, t_done
            if in_flight is None:
                return
            s, mets, t_start = in_flight
            in_flight = None
            jax.block_until_ready(mets)
            now = time.perf_counter()
            dt, t_done = now - max(t_done, t_start), now
            self._watchdog(s, dt)
            if s % cfg.log_every == 0 or s == cfg.steps - 1:
                self.history.append({
                    "step": s, **{k: float(v) for k, v in mets.items()},
                    **payload_mets, "sec": dt})

        with ctx:
            for step in range(start_step, cfg.steps):
                t0 = time.perf_counter()
                with jax.profiler.StepTraceAnnotation("train.step",
                                                      step_num=step):
                    batch = jax.tree.map(jnp.asarray, self.data_fn(step))
                    srng = jax.random.fold_in(rng, step)
                    if self._use_dp:
                        values, opt_state, err_state, mets = train_step(
                            values, opt_state, err_state, batch, srng)
                    else:
                        values, opt_state, mets = train_step(
                            values, opt_state, batch, srng)
                    settle()
                    in_flight = (step, mets, t0)
                    done_step = step + 1
                    if ckpt and cfg.ckpt_every and \
                            (step + 1) % cfg.ckpt_every == 0:
                        settle()
                        ckpt.save(_ckpt_state(), step + 1,
                                  metadata=ckpt_meta)
                        last_saved = step + 1
                    if self._preempted:
                        settle()
                        if ckpt and last_saved != step + 1:
                            ckpt.save(_ckpt_state(), step + 1,
                                      metadata=ckpt_meta)
                            ckpt.wait()
                            last_saved = step + 1
                        break
                    if self.eval_fn and cfg.eval_every and \
                            (step + 1) % cfg.eval_every == 0:
                        settle()
                        params = nn.with_values(params_meta, values)
                        ev = self.eval_fn(params)
                        self.history.append({"step": step, **{
                            f"eval_{k}": float(v) for k, v in ev.items()}})
                        metric = float(next(iter(ev.values())))
                        if cfg.early_stop_patience:
                            if metric > best_metric + 1e-6:
                                best_metric, stale = metric, 0
                            else:
                                stale += 1
                                if stale >= cfg.early_stop_patience:
                                    break
            settle()
        if ckpt:
            if last_saved != done_step:
                ckpt.save(_ckpt_state(), done_step,
                          metadata=ckpt_meta)
            ckpt.wait()                    # drain the async writer
        self.done_step = done_step
        self.err_state = err_state
        problems = validate_history(self.history[hist_start:])
        if problems:
            raise RuntimeError(
                "train history failed schema validation "
                "(repro.train.metrics.HISTORY_SCHEMA):\n  "
                + "\n  ".join(problems))
        return nn.with_values(params_meta, values), self.history

    def _fsdp_layout(self, values, opt_state, err_state):
        """Lay freshly-initialised state out per the fsdp sharding rule
        (V-divisible float leaves row-sharded over the data axes, error
        rows over the virtual-shard axis).  Restore re-lays checkpoints
        the same way via ``_state_shardings``."""
        from jax.sharding import NamedSharding
        values = jax.device_put(values, compression.fsdp_shardings(
            values, self.mesh, self._accum))
        opt_state = jax.device_put(opt_state, compression.fsdp_shardings(
            opt_state, self.mesh, self._accum))
        if err_state is not None:
            row = NamedSharding(self.mesh,
                                compression.dp_partition_spec(self.mesh))
            err_state = jax.device_put(
                err_state, jax.tree.map(lambda _: row, err_state))
        return values, opt_state, err_state

    def _state_shardings(self, params_meta, state):
        """Target shardings for (elastic) checkpoint restore, matching
        whatever subtrees ``state`` carries.  The dp path keeps
        params/opt replicated (row-sharded under fsdp) and rows the
        error-feedback state over the data axes; the jit path reuses
        the logical-axis resolution."""
        from jax.sharding import NamedSharding, PartitionSpec
        repl = NamedSharding(self.mesh, PartitionSpec())
        sh = {}
        for key, tree in state.items():
            if key == "err":
                err_sh = NamedSharding(
                    self.mesh, compression.dp_partition_spec(self.mesh))
                sh[key] = jax.tree.map(lambda _: err_sh, tree)
            elif self._use_dp:
                if self._fsdp and key in ("values", "opt"):
                    sh[key] = compression.fsdp_shardings(
                        tree, self.mesh, self._accum)
                else:
                    sh[key] = jax.tree.map(lambda _: repl, tree)
            elif key == "values":
                sh[key] = dist.params_shardings(params_meta, self.mesh,
                                                self.rules)
            else:                                   # "opt"
                sh[key] = _opt_shardings(tree, params_meta, self.mesh,
                                         self.rules)
        return sh

    def _watchdog(self, step, dt):
        self._step_times.append(dt)
        if len(self._step_times) >= 20:
            med = float(np.median(self._step_times[-100:]))
            if dt > self.cfg.watchdog_factor * med and step > 20:
                self.history.append(
                    {"step": step, "straggler_sec": dt, "median_sec": med})


class _nullctx:
    def __enter__(self):
        return self

    def __exit__(self, *a):
        return False


def _opt_shardings(opt_state, params_meta, mesh, rules):
    from jax.sharding import NamedSharding, PartitionSpec
    psh = dist.params_shardings(params_meta, mesh, rules)

    def _match(slot_tree):
        return jax.tree.map(
            lambda s, p: p if s.ndim > 0 and s.size > 0
            else NamedSharding(mesh, PartitionSpec()),
            slot_tree, psh)
    return {
        "m": _match(opt_state["m"]),
        "v": _match(opt_state["v"]),
        "step": NamedSharding(mesh, PartitionSpec()),
    }

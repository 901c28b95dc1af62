"""Pure-JAX optimizers (no optax in this environment).

Operates on *value* pytrees (repro.nn.module.values output).  Non-float
leaves (e.g. the frozen RecJPQ codebook ints) are carried through
untouched: their moment slots are 0-size arrays and their "grads"
(float0 from ``jax.grad(..., allow_int=True)``) are ignored.

Optimizer state is a plain pytree -> checkpointable and shardable with
the same logical-axis rules as the parameters (FSDP over the data axis
happens for free because moments inherit each param's sharding).
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class OptConfig:
    kind: str = "adamw"          # adamw | adam | sgd
    lr: float = 1e-3
    b1: float = 0.9
    b2: float = 0.999
    eps: float = 1e-8
    weight_decay: float = 0.0
    clip_norm: Optional[float] = 1.0
    schedule: str = "constant"   # constant | cosine | linear_warmup_cosine
    warmup_steps: int = 0
    total_steps: int = 10_000
    min_lr_frac: float = 0.1
    # data-parallel gradient exchange: "none" | "bf16" | "int8".  Under a
    # mesh, a non-"none" method (or TrainConfig.grad_compression /
    # grad_accum_shards) routes the Trainer through the elastic-
    # deterministic compressed exchange (repro.dist.compression) instead
    # of the implicit fp32 all-reduce of jit sharding.
    grad_compression: str = "none"


def _is_float(x) -> bool:
    return jnp.issubdtype(jnp.asarray(x).dtype, jnp.floating)


def schedule_lr(cfg: OptConfig, step):
    step = step.astype(jnp.float32) if hasattr(step, "astype") else float(step)
    lr = jnp.asarray(cfg.lr, jnp.float32)
    if cfg.schedule == "constant":
        sched = jnp.ones(())
    else:
        warm = jnp.clip(step / jnp.maximum(cfg.warmup_steps, 1), 0.0, 1.0) \
            if cfg.warmup_steps > 0 else 1.0
        prog = jnp.clip((step - cfg.warmup_steps)
                        / jnp.maximum(cfg.total_steps - cfg.warmup_steps, 1),
                        0.0, 1.0)
        cos = 0.5 * (1.0 + jnp.cos(jnp.pi * prog))
        cos = cfg.min_lr_frac + (1.0 - cfg.min_lr_frac) * cos
        sched = warm * cos if cfg.schedule.endswith("cosine") else warm
    return lr * sched


def init_opt_state(values):
    def _slot(x):
        if _is_float(x):
            return jnp.zeros_like(x)
        return jnp.zeros((0,), jnp.float32)
    return {
        "m": jax.tree.map(_slot, values),
        "v": jax.tree.map(_slot, values),
        "step": jnp.zeros((), jnp.int32),
    }


def global_norm(grads):
    leaves = [jnp.sum(jnp.square(g.astype(jnp.float32)))
              for g in jax.tree.leaves(grads) if _is_float(g) and g.size]
    return jnp.sqrt(sum(leaves)) if leaves else jnp.zeros(())


def apply_updates(cfg: OptConfig, state, values, grads, *,
                  grad_norm=None):
    """Returns (new_values, new_state, stats).

    Every per-parameter op is elementwise, so the update runs unchanged
    on FSDP row-slices: the fsdp combine module calls this on each
    device's owned slice and injects the bitwise-deterministic global
    norm via ``grad_norm=`` (when ``None`` the norm is computed here
    from the full grads tree).

    ``weight_decay`` is **decoupled** (Loshchilov & Hutter) for every
    kind — added to the update after the gradient/moment term, scaled
    by the scheduled lr but not by the clip scale.  Historically sgd
    and adam silently ignored it, so a sweep cell setting
    ``kind="sgd", weight_decay=0.1`` trained undecayed.
    """
    with jax.named_scope("train.optimizer"):
        step = state["step"] + 1
        lr = schedule_lr(cfg, step)
        gn = global_norm(grads) if grad_norm is None else grad_norm
        scale = jnp.ones(())
        if cfg.clip_norm is not None:
            scale = jnp.minimum(1.0, cfg.clip_norm / jnp.maximum(gn, 1e-9))

        b1, b2, eps = cfg.b1, cfg.b2, cfg.eps
        t = step.astype(jnp.float32)
        bc1 = 1.0 - b1 ** t
        bc2 = 1.0 - b2 ** t

        def _upd(p, g, m, v):
            if not _is_float(p):
                return p, m, v
            g = g.astype(jnp.float32) * scale
            p32 = p.astype(jnp.float32)
            if cfg.kind == "sgd":
                update = g
            else:
                m = b1 * m + (1.0 - b1) * g
                v = b2 * v + (1.0 - b2) * jnp.square(g)
                update = (m / bc1) / (jnp.sqrt(v / bc2) + eps)
            if cfg.weight_decay > 0:
                update = update + cfg.weight_decay * p32
            new_p = p32 - lr * update
            return new_p.astype(p.dtype), m, v

        flat_p, treedef = jax.tree.flatten(values)
        flat_g = treedef.flatten_up_to(grads)
        flat_m = treedef.flatten_up_to(state["m"])
        flat_v = treedef.flatten_up_to(state["v"])
        out = [_upd(p, g, m, v) for p, g, m, v in
               zip(flat_p, flat_g, flat_m, flat_v)]
        new_values = treedef.unflatten([o[0] for o in out])
        new_state = {
            "m": treedef.unflatten([o[1] for o in out]),
            "v": treedef.unflatten([o[2] for o in out]),
            "step": step,
        }
    return new_values, new_state, {"grad_norm": gn, "lr": lr}

#!/usr/bin/env python3
"""Smoke run of the main path on one TPU chip.

    python3 chip_smoke.py [--seed 0]
    python3 chip_smoke.py --four-chip [--seed 0]

All phases run in this one process, through the objects a user calls;
data and weights come from ``--seed``.

train   SASRec with RecJPQ at the paper's widths (d_model 512, max_len
        200, 2 layers, 4 heads, d_ff 1024; codes m=8, b=256 by SVD
        assignment) on a Booking.com-sized catalogue (34,742 items),
        full-softmax loss, batch 64, ~20 steps through ``Trainer`` and
        its ``TrainSpec``.  The loss must be finite and fall.
serve   the trained params through ``model.bind_engine`` (fused PQTopK,
        pruning off, then on with a prebuilt pruning state), against
        ``lax.top_k(score_last(...))``; then ``jpq_topk_lut`` at the
        Gowalla catalogue size (1,280,969 items, random codes, B=32,
        k=100), unpruned and pruned, against ``lax.top_k(jpq.logits)``.
        Ids must match the float32 reference except among values tied
        within float32 rounding, and every compiled serve program must
        hold the Mosaic kernel (``tpu_custom_call``).

``--four-chip`` runs only the paths that span chips, each against the
same call on one device: the catalogue row-sharded over a 4-way model
mesh (pruned, cross-shard threshold exchange), and one elastic int8
gradient-exchange step (dp, then fsdp) on a (4, 1) mesh against a
(1, 1) mesh with the same ``accum_shards=4`` (bitwise by contract).

The script fails, and prints no result line, when JAX finds no TPU or
any check fails.  Its last line on success is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

from repro.launch import compile_cache  # noqa: E402

# Booking.com and Gowalla item counts (the paper's Table 1)
BOOKING_ITEMS = 34_742
GOWALLA_ITEMS = 1_280_969
# Gowalla rounded down so that four shards of 512-row tiles cover it
FOUR_CHIP_ITEMS = 1_280_000

PAPER_WIDTHS = dict(d_model=512, max_len=200, n_layers=2, n_heads=4,
                    d_ff=1024, m=8, b=256)


def log(msg: str) -> None:
    print(msg, flush=True)


def check(ok: bool, msg: str) -> None:
    if not ok:
        raise RuntimeError(msg)


class CompileClock:
    """Sums the backend compile seconds JAX reports, so each phase can
    print what it spent compiling."""

    def __init__(self):
        import jax
        self.total = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.total += duration


def peak_bytes():
    """The allocator's peak of live buffers on device 0.  On TPU it does
    not count a program's own temporaries, which ``memory_analysis()``
    of its compile reports."""
    import jax
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


# ------------------------------------------------------------ train

def train_phase(*, n_items: int, d_model: int, max_len: int, n_layers: int,
                n_heads: int, d_ff: int, m: int, b: int, batch: int,
                steps: int, n_users: int, seed: int, lr: float = 1e-3):
    """Train SASRec + RecJPQ for ``steps`` steps the way
    ``launch/train.py`` builds it.  Returns (model, params, data,
    report); raises if the loss is not finite or does not fall."""
    import numpy as np
    from repro.core import EmbeddingConfig, build_codebook
    from repro.data.sequences import SeqDataConfig, SyntheticSequences
    from repro.models.sequential import SeqRecConfig, SeqRecModel
    from repro.train.loop import TrainConfig, Trainer
    from repro.train.optimizer import OptConfig
    from repro.train.spec import TrainSpec

    t0 = time.perf_counter()
    data = SyntheticSequences(SeqDataConfig(
        n_users=n_users, n_items=n_items, seq_len=max_len, seed=seed))
    users, items = data.train_interactions()
    codes = build_codebook("svd", n_items + 2, m, b,
                           interactions=(users, items + 1),
                           n_users=data.n_users_eff, seed=seed)
    cfg = SeqRecConfig(arch="sasrec", n_items=n_items, max_len=max_len,
                       d_model=d_model, n_layers=n_layers, n_heads=n_heads,
                       d_ff=d_ff, loss="full_ce",
                       embedding=EmbeddingConfig(0, 0, kind="jpq", m=m,
                                                 b=b))
    model = SeqRecModel(cfg, codes=codes)
    setup_s = time.perf_counter() - t0

    # the loop asks for batch s once step s-1 has finished (log_every=1
    # reads every loss back), so the gaps between calls are step times
    starts = []

    def data_fn(s):
        starts.append(time.perf_counter())
        return data.train_batch(s, batch)

    trainer = Trainer(model, OptConfig(lr=lr),
                      TrainConfig(steps=steps, batch_size=batch,
                                  log_every=1, eval_every=0, seed=seed),
                      data_fn=data_fn, spec=TrainSpec())
    params, hist = trainer.run()
    end = time.perf_counter()
    losses = [h["loss"] for h in hist if "loss" in h]
    check(len(losses) == steps, f"{len(losses)} losses for {steps} steps")
    check(bool(np.all(np.isfinite(losses))), f"non-finite loss: {losses}")
    n = max(1, steps // 4)
    head, tail = float(np.mean(losses[:n])), float(np.mean(losses[-n:]))
    check(tail < head, f"loss did not fall: mean of the first {n} "
                       f"losses {head}, of the last {n} {tail}")
    warm = 2                  # step 0 compiles; step 1 settles donation
    report = {
        "setup_s": setup_s,
        "first_step_s": starts[1] - starts[0],
        "steps_per_s": (steps - warm) / (end - starts[warm]),
        "losses": losses,
        "peak_bytes": peak_bytes(),
    }
    return model, params, data, report


# ------------------------------------------------------------ serve

def compare_topk(v, i, ref_v, ref_i, ref_scores, what: str) -> dict:
    """Check a retrieved (values, ids) [B, k] against the float32
    reference top-k: the values agree within float32 rounding, every
    returned id scores its returned value in the reference matrix, and
    no id repeats in a row.  Ids may then differ from the reference's
    only among values tied within that rounding."""
    import numpy as np
    v, i = np.asarray(v), np.asarray(i)
    ref_v, ref_i = np.asarray(ref_v), np.asarray(ref_i)
    ref_scores = np.asarray(ref_scores)
    check(v.shape == ref_v.shape and i.shape == ref_i.shape,
          f"{what}: shape {v.shape} vs reference {ref_v.shape}")
    check(bool(np.all(np.isfinite(v))), f"{what}: non-finite values")
    tol = 8 * np.finfo(np.float32).eps * np.maximum(
        np.max(np.abs(ref_v), axis=1, keepdims=True), 1.0)
    dv = np.abs(v - ref_v)
    check(bool(np.all(dv <= tol)),
          f"{what}: values differ from the reference by up to {dv.max()}")
    own = np.take_along_axis(ref_scores, i, axis=1)
    check(bool(np.all(np.abs(own - v) <= tol)),
          f"{what}: a returned id does not score its returned value")
    for row in i:
        check(len(set(row.tolist())) == row.size, f"{what}: repeated id")
    same = i == ref_i
    return {"ids_equal": float(same.mean()), "max_abs_dv": float(dv.max()),
            "bitwise": bool(np.array_equal(v, ref_v) and same.all())}


def state_arrays(state) -> dict:
    """The arrays of a ``PruneState``, to pass into a jitted function
    (which rebuilds the state with ``_replace``).  Arrays a jitted
    function closes over are compiled into the program as constants:
    tens of MB at catalogue scale, which the compiler then folds."""
    if state is None:
        return {}
    return {"codes": state.codes, "ids": state.ids,
            "present": state.present}


def _compiled(f, *args):
    """Lower and compile ``f`` for ``args``; returns (compiled, HLO
    text, compile seconds)."""
    import jax
    t0 = time.perf_counter()
    c = jax.jit(f).lower(*args).compile()
    return c, c.as_text(), time.perf_counter() - t0


def serve_phase(model, params, seqs, *, k: int = 10, backend=None) -> dict:
    """Serve ``seqs`` through ``bind_engine`` with pruning off and on,
    against ``lax.top_k(score_last)``.  Returns one report per mode
    with ``kernel`` = whether the compiled program holds the Mosaic
    kernel."""
    import jax
    import jax.numpy as jnp
    from repro.core import engine
    from repro.core.engine import RetrievalSpec

    scores = jax.jit(model.score_last)(params, seqs)
    ref_v, ref_i = jax.lax.top_k(scores, k)
    emb = params["item_emb"]
    state = engine.build_prune_state(emb["codes"].value,
                                     emb["centroids"].shape[1])
    out = {}
    for name, st in (("fused", None), ("pruned", state)):
        spec = RetrievalSpec(kind="jpq", k=k, fused=True,
                             prune=st is not None, backend=backend)

        def retrieve(p, seq, arrays, spec=spec, st=st):
            bound = model.bind_engine(p, spec)
            if st is not None:
                bound.engine.bind_catalogue(prune=st._replace(**arrays))
            return bound.retrieve(seq)

        c, hlo, comp_s = _compiled(retrieve, params, seqs,
                                   state_arrays(st))
        v, i = c(params, jnp.asarray(seqs), state_arrays(st))
        rep = compare_topk(v, i, ref_v, ref_i, scores, f"serve/{name}")
        out[name] = dict(rep, compile_s=comp_s,
                         kernel="tpu_custom_call" in hlo)
    return out


def catalogue_phase(*, n_items: int, batch: int, k: int, d: int, m: int,
                    b: int, seed: int, backend=None) -> dict:
    """``jpq_topk_lut`` over a random catalogue of ``n_items`` codes,
    unpruned and pruned, against ``lax.top_k(jpq.logits)``.  Each
    report also says whether every returned value is exactly the
    split-order float32 sum of its LUT entries, summed on the host
    (``exact``), and the same of the reference's top-k
    (``reference_exact``)."""
    import jax
    import numpy as np
    from repro.core import engine, jpq
    from repro.kernels.jpq_topk import ops as tops
    from repro.nn.module import KeyGen

    p = jpq.init(KeyGen(seed), n_items, d, m, b)
    h = jax.random.normal(jax.random.PRNGKey(seed + 1), (batch, d))
    part = jax.jit(jpq.partial_scores)(p, h)
    scores = jax.jit(jpq.logits)(p, h)
    ref_v, ref_i = jax.lax.top_k(scores, k)
    codes = p["codes"].value
    lut, cds = np.asarray(part), np.asarray(codes).astype(np.int64)
    rows = np.arange(batch)[:, None]

    def exact(v, i):
        i = np.asarray(i)
        s = lut[rows, 0, cds[i, 0]]
        for j in range(1, m):
            s = s + lut[rows, j, cds[i, j]]          # float32, in order
        return bool(np.array_equal(np.asarray(v), s))

    state = engine.build_prune_state(codes, b)
    out = {}
    for name, st in (("fused", None), ("pruned", state)):
        def topk(q, cd, arrays, st=st):
            prune = None if st is None else st._replace(**arrays)
            return tops.jpq_topk_lut(q, cd, k, backend=backend,
                                     prune=prune)

        c, hlo, comp_s = _compiled(topk, part, codes, state_arrays(st))
        v, i = c(part, codes, state_arrays(st))
        rep = compare_topk(v, i, ref_v, ref_i, scores,
                           f"catalogue/{name}")
        out[name] = dict(rep, exact=exact(v, i),
                         reference_exact=exact(ref_v, ref_i),
                         compile_s=comp_s, kernel="tpu_custom_call" in hlo)
    return out


# --------------------------------------------------------- four chips

def sharded_catalogue_phase(devices, *, n_items: int, batch: int, k: int,
                            d: int, m: int, b: int, seed: int) -> dict:
    """Pruned fused top-k over a catalogue row-sharded across
    ``devices`` (cross-shard threshold exchange) against the same call
    on one device.  Both are exact, so they must agree bitwise."""
    import jax
    import numpy as np
    from repro import dist
    from repro.core import engine, jpq, sharded
    from repro.launch.mesh import make_mesh
    from repro.nn.module import KeyGen

    p = jpq.init(KeyGen(seed), n_items, d, m, b)
    h = jax.random.normal(jax.random.PRNGKey(seed + 1), (batch, d))
    part = jpq.partial_scores(p, h)
    codes = p["codes"].value
    shards = len(devices)
    state = engine.build_prune_state(codes, b, shards=shards)

    arrays = state_arrays(state)

    def call():
        # a fresh function per use: the ambient mesh rules are not part
        # of jit's cache key, so one function object would replay the
        # one-device trace under the mesh
        return lambda q, cd, a: sharded.fused_topk_over_codes(
            q, cd, k, prune=state._replace(**a), return_stats=True)

    one_v, one_i, one_st = jax.jit(call())(part, codes, arrays)
    mesh = make_mesh((1, shards), ("data", "model"), devices=devices)
    with dist.use_mesh_rules(mesh):
        c, hlo, comp_s = _compiled(call(), part, codes, arrays)
        v, i, st = c(part, codes, arrays)
    check("all-gather" in hlo, "the sharded program gathers no candidates")
    same = (np.array_equal(np.asarray(v), np.asarray(one_v))
            and np.array_equal(np.asarray(i), np.asarray(one_i)))
    check(same, "sharded pruned top-k differs from the one-device call")
    return {"bitwise": same, "compile_s": comp_s,
            "kernel": "tpu_custom_call" in hlo,
            "skipped_tiles": int(st["skipped_tiles"]),
            "total_tiles": int(st["total_tiles"]),
            "one_device_skipped": int(one_st["skipped_tiles"])}


def elastic_phase(devices, *, fsdp: bool, steps: int, seed: int,
                  widths: dict, n_items: int, batch: int) -> dict:
    """``steps`` elastic int8 exchange steps on a (len(devices), 1)
    mesh against a (1, 1) mesh, both with ``accum_shards=4``; the
    parameters must agree bitwise."""
    import jax
    import numpy as np
    from repro.core import EmbeddingConfig
    from repro.data.sequences import SeqDataConfig, SyntheticSequences
    from repro.launch.mesh import make_mesh
    from repro.models.sequential import SeqRecConfig, SeqRecModel
    from repro.nn import module as nn
    from repro.train.loop import TrainConfig, Trainer
    from repro.train.optimizer import OptConfig
    from repro.train.spec import TrainSpec

    w = widths
    data = SyntheticSequences(SeqDataConfig(
        n_users=2000, n_items=n_items, seq_len=w["max_len"], seed=seed))
    cfg = SeqRecConfig(arch="sasrec", n_items=n_items,
                       max_len=w["max_len"], d_model=w["d_model"],
                       n_layers=w["n_layers"], n_heads=w["n_heads"],
                       d_ff=w["d_ff"], loss="full_ce",
                       embedding=EmbeddingConfig(0, 0, kind="jpq",
                                                 m=w["m"], b=w["b"]))
    spec = TrainSpec(compression="int8", accum_shards=4, fsdp=fsdp,
                     elastic=True)

    def run(devs):
        mesh = make_mesh((len(devs), 1), ("data", "model"), devices=devs)
        tr = Trainer(SeqRecModel(cfg), OptConfig(lr=1e-3),
                     TrainConfig(steps=steps, batch_size=batch,
                                 log_every=1, eval_every=0, seed=seed),
                     data_fn=lambda s: data.train_batch(s, batch),
                     mesh=mesh, spec=spec)
        params, hist = tr.run()
        vals = jax.tree.map(np.asarray, nn.values(params))
        return vals, [h["loss"] for h in hist if "loss" in h]

    many, loss_many = run(list(devices))
    one, loss_one = run(list(devices[:1]))
    same = all(np.array_equal(a, c) for a, c in
               zip(jax.tree.leaves(many), jax.tree.leaves(one)))
    check(same, f"elastic int8 ({'fsdp' if fsdp else 'dp'}) step differs "
                f"between {len(devices)} devices and one")
    return {"bitwise": same, "losses": loss_many}


# ------------------------------------------------------------- main

def require_tpu():
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"chip_smoke: JAX finds no TPU (platform "
                         f"{devs[0].platform!r}); nothing was run")
    return devs


def one_chip(seed: int, clock: CompileClock) -> None:
    import jax
    import jax.numpy as jnp
    w = PAPER_WIDTHS
    c0 = clock.total
    model, params, data, rep = train_phase(
        n_items=BOOKING_ITEMS, batch=64, steps=20, n_users=20_000,
        seed=seed, **w)
    log(f"train: n_items={BOOKING_ITEMS} batch=64 "
        f"steps={len(rep['losses'])} setup_s={rep['setup_s']} "
        f"first_step_s={rep['first_step_s']} "
        f"steps_per_s={rep['steps_per_s']} "
        f"compile_s={clock.total - c0} peak_bytes={rep['peak_bytes']}")
    log(f"train: losses={rep['losses']}")
    log(f"train: memory_stats={jax.devices()[0].memory_stats()}")

    ev = data.eval_batch(range(0, 32), split="test")
    seqs = jnp.asarray(ev["seq"])
    c0 = clock.total
    serve = serve_phase(model, params, seqs, k=10)
    for name, r in serve.items():
        log(f"serve/{name}: B=32 k=10 {json.dumps(r)}")
        check(r["kernel"], f"serve/{name}: no tpu_custom_call in the "
                           f"compiled program")
    log(f"serve: compile_s={clock.total - c0}")

    c0 = clock.total
    cat = catalogue_phase(n_items=GOWALLA_ITEMS, batch=32, k=100,
                          d=w["d_model"], m=w["m"], b=w["b"], seed=seed)
    for name, r in cat.items():
        log(f"catalogue/{name}: N={GOWALLA_ITEMS} B=32 k=100 "
            f"{json.dumps(r)}")
        check(r["kernel"], f"catalogue/{name}: no tpu_custom_call in "
                           f"the compiled program")
    log(f"catalogue: compile_s={clock.total - c0} "
        f"peak_bytes={peak_bytes()}")


def four_chips(devices, seed: int, clock: CompileClock) -> None:
    check(len(devices) >= 4, f"--four-chip needs 4 devices, JAX finds "
                             f"{len(devices)}")
    devs = devices[:4]
    w = PAPER_WIDTHS
    c0 = clock.total
    r = sharded_catalogue_phase(devs, n_items=FOUR_CHIP_ITEMS, batch=32,
                                k=100, d=w["d_model"], m=w["m"], b=w["b"],
                                seed=seed)
    log(f"four-chip/sharded-catalogue: N={FOUR_CHIP_ITEMS} B=32 k=100 "
        f"{json.dumps(r)} compile_s={clock.total - c0}")
    check(r["kernel"], "sharded catalogue: no tpu_custom_call")
    for fsdp in (False, True):
        c0 = clock.total
        r = elastic_phase(devs, fsdp=fsdp, steps=2, seed=seed, widths=w,
                          n_items=BOOKING_ITEMS, batch=64)
        log(f"four-chip/elastic-int8-{'fsdp' if fsdp else 'dp'}: "
            f"{json.dumps(r)} compile_s={clock.total - c0}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chip", action="store_true",
                    help="run only the cross-chip paths, on 4 chips")
    args = ap.parse_args(argv)

    devices = require_tpu()
    cache = compile_cache.enable()
    warm = os.path.isdir(cache) and bool(os.listdir(cache))
    import jax
    log(f"jax {jax.__version__}; device {devices[0].device_kind} x "
        f"{len(devices)}; compile cache {cache} "
        f"({'warm' if warm else 'cold'})")
    clock = CompileClock()
    if args.four_chip:
        four_chips(devices, args.seed, clock)
        count = 4
    else:
        one_chip(args.seed, clock)
        count = len(devices)
    log(f"total compile_s={clock.total}")
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": count}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

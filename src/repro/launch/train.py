"""Production training entrypoint.

    PYTHONPATH=src python -m repro.launch.train --arch sasrec \
        --steps 300 --ckpt-dir /tmp/ckpt [--devices 8 --model-axis 2] \
        [--grad-compression bf16] [--overlap backward]

Paper backbones (sasrec / bert4rec / gru4rec) train on the synthetic
sequence pipeline with RecJPQ selectable via --embedding; assigned archs
train their reduced smoke configs (full configs are cluster-scale — the
dry-run covers them).  --devices N > 1 forks host devices (CPU SPMD) and
runs the same pjit path a TPU pod would.

The training-policy flags (--grad-compression / --grad-accum-shards /
--fsdp / --overlap / --microbatches) are the shared TrainSpec cluster
from ``repro.train.spec.add_train_spec_args`` — the same spellings
``launch/dryrun.py`` takes — and resolve to one declarative
``TrainSpec`` via ``spec_from_args``.

Fault-tolerance knobs exercised here: --ckpt-every (atomic async saves,
each stamped with the spec's layout fingerprint), SIGTERM ->
save-and-exit, automatic resume from --ckpt-dir (layout-verified
against the stamp).  With --grad-compression (and a fixed
--grad-accum-shards) the resume may use a *differently-sized* mesh:
``--mesh 4`` after an 8-device run restores params, opt state and
error-feedback state onto the new mesh and continues bit-identically to
an uninterrupted run (elastic restore, docs/sharding.md).  --fsdp
additionally row-shards params, optimizer moments and error state
across the data axes and turns each exchange round's all-gather into a
reduce-scatter-sized all-to-all; --overlap picks the host round
schedule (serial / double-buffered dispatch / backward-overlapped) —
a pure wall-clock knob, every mode bitwise identical, so an
interrupted --overlap backward run may even resume under a different
mode.  The elastic contract is preserved throughout: an --fsdp run
killed on 8 devices resumes bit-identically on 4.
"""
import argparse
import os
import sys

from repro.train.spec import add_train_spec_args, spec_from_args


def build_parser() -> argparse.ArgumentParser:
    """The CLI surface, extracted so tests can assert flag parity with
    the dryrun CLI.  Must stay importable before jax / XLA_FLAGS."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="sasrec")
    ap.add_argument("--embedding", default="jpq",
                    choices=["full", "jpq", "qr"])
    ap.add_argument("--assignment", default="svd",
                    choices=["svd", "bpr", "random"])
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--batch-size", type=int, default=64)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--m", type=int, default=8)
    ap.add_argument("--d-model", type=int, default=64)
    ap.add_argument("--n-items", type=int, default=2000)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=100)
    ap.add_argument("--eval-every", type=int, default=100)
    ap.add_argument("--early-stop-patience", type=int, default=0)
    ap.add_argument("--devices", type=int, default=1,
                    help="forked host devices for SPMD (CPU)")
    ap.add_argument("--mesh", type=int, default=None,
                    help="alias for --devices; spell the restart of a "
                         "preempted run on a differently-sized mesh")
    ap.add_argument("--model-axis", type=int, default=1)
    add_train_spec_args(ap)        # the shared TrainSpec flag cluster
    ap.add_argument("--seed", type=int, default=0)
    return ap


def main():
    args = build_parser().parse_args()
    spec = spec_from_args(args)

    if args.mesh is not None:
        args.devices = args.mesh
    if args.devices > 1:
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={args.devices}"
        ).strip()

    from repro.launch import compile_cache
    compile_cache.enable()
    import jax
    import jax.numpy as jnp
    from repro.configs import list_archs, get_bundle
    from repro.core import EmbeddingConfig, build_codebook
    from repro.data.sequences import SeqDataConfig, SyntheticSequences
    from repro.launch.mesh import make_host_mesh
    from repro.models.sequential import SeqRecConfig, SeqRecModel
    from repro.train.loop import TrainConfig, Trainer
    from repro.train.metrics import ndcg_at_k
    from repro.train.optimizer import OptConfig

    mesh = None
    if args.devices > 1 or spec.elastic:
        # the elastic path needs a mesh even single-device (a (1, 1)
        # host mesh: one data shard, V accumulation rounds)
        mesh = make_host_mesh(args.devices, args.model_axis)
        print(f"mesh: {dict(mesh.shape)}")

    if args.arch in ("sasrec", "bert4rec", "gru4rec"):
        data = SyntheticSequences(SeqDataConfig(
            n_users=max(args.n_items, 500), n_items=args.n_items,
            seq_len=32, seed=args.seed))
        codes = None
        emb = None
        if args.embedding != "full":
            emb = EmbeddingConfig(0, 0, kind=args.embedding, m=args.m,
                                  b=256)
        if args.embedding == "jpq":
            u, i = data.train_interactions()
            codes = build_codebook(
                args.assignment, args.n_items + 2, args.m, 256,
                interactions=(u, i + 1), n_users=data.n_users_eff,
                seed=args.seed,
                **({"epochs": 3} if args.assignment == "bpr" else {}))
        cfg = SeqRecConfig(arch=args.arch, n_items=args.n_items,
                           max_len=32, d_model=args.d_model, n_layers=2,
                           n_heads=2, d_ff=2 * args.d_model,
                           embedding=emb)
        model = SeqRecModel(cfg, codes=codes)

        if args.arch == "bert4rec":
            from repro.models.sequential import mask_batch

            def data_fn(s):
                b = data.train_batch(s, args.batch_size)
                seq = jnp.asarray(b["seq"])
                ms, tg = mask_batch(jax.random.PRNGKey(s), seq,
                                    cfg.mask_prob, cfg.mask_id)
                return {"seq": ms, "targets": tg}
        else:
            def data_fn(s):
                return data.train_batch(s, args.batch_size)

        ev = data.eval_batch(range(0, data.n_users_eff, 8), split="val")
        ev = {k: jnp.asarray(v) for k, v in ev.items()}
        score = jax.jit(model.score_last)

        def eval_fn(params):
            s = score(params, ev["seq"])
            return {"ndcg10": float(jnp.mean(ndcg_at_k(s, ev["target"])))}
    else:
        bundle = get_bundle(args.arch)
        model, batch, _ = bundle.make_smoke()
        batch = {k: jnp.asarray(v) for k, v in batch.items()}
        data_fn = lambda s: batch            # noqa: E731
        eval_fn = None
        print(f"arch {args.arch}: training the reduced smoke config "
              f"({bundle.description}); full config is dry-run only")

    # the legacy TrainConfig knobs are populated alongside the explicit
    # spec — both resolve to the same TrainSpec by construction, which
    # the Trainer verifies (its conflict check would catch a drift
    # between the flag cluster and the legacy fields)
    tr = Trainer(model, OptConfig(lr=args.lr),
                 TrainConfig(steps=args.steps, batch_size=args.batch_size,
                             log_every=max(args.steps // 10, 1),
                             eval_every=args.eval_every,
                             ckpt_dir=args.ckpt_dir,
                             ckpt_every=args.ckpt_every,
                             early_stop_patience=args.early_stop_patience,
                             microbatches=args.microbatches,
                             grad_compression=args.grad_compression,
                             grad_accum_shards=args.grad_accum_shards,
                             fsdp=args.fsdp,
                             overlap=args.overlap,
                             seed=args.seed),
                 data_fn=data_fn, eval_fn=eval_fn, mesh=mesh, spec=spec)
    _, hist = tr.run()
    for h in hist[-5:]:
        print(h)
    if tr._preempted:
        print(f"preempted: checkpoint stamped at step {tr.done_step}; "
              f"resume with the same --ckpt-dir (any mesh size whose "
              f"data-parallel degree divides the accum shards)")
    else:
        print(f"done at step {tr.done_step}")


if __name__ == "__main__":
    main()

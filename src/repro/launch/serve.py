"""Serving entrypoint: batched retrieval / scoring replica loop.

    PYTHONPATH=src python -m repro.launch.serve --arch two-tower-retrieval-jpq \
        --requests 20 --batch-size 64 --fused --prune --perm --warm-theta

Loads the arch's smoke config (or a checkpoint via --ckpt-dir), jits the
serve program, and drives batched requests through it, reporting
latency percentiles — the serve_p99 cell's runnable counterpart.

Every request carries *fresh* ids (``make_requests``): replaying one
tiled batch — what this loop used to do — measures a cached dispatch of
identical device buffers, not realistic serving, and under-reports
p50/p99.  ``--seed`` makes the request stream reproducible.  For archs
with a ``retrieve`` serve path, ``--fused/--no-fused`` switches between
the PQTopK fused score+top-k path and the materialise-then-top-k
reference (docs/serving.md); ``--prune`` adds score-bound dynamic
pruning (the PruneState is built ONCE, mesh-aware, outside the
per-request jit), ``--perm`` sweeps in popularity order (tallied from
the request template's id histogram — the serving stand-in for
train-set counts), ``--warm-theta [decay]`` seeds each request's
threshold from a ``ThresholdState`` EMA, and ``--mesh S`` runs the
whole loop on an S-way model-sharded host mesh (permute-then-shard
pruned serving).  With pruning on, the loop reports the skip fraction
aggregated across ALL shards (mean weighted by local tile count, the
``fused_topk_over_codes`` stats contract) — not shard 0's.
"""
import argparse
import os
import sys
import time

import numpy as np


def _set_mesh_env(argv) -> None:
    """Set the host-device-count XLA flag from a raw ``--mesh`` argv
    peek BEFORE anything imports jax (``build_parser`` pulls in
    ``repro.core.engine``; the flag must be in place first)."""
    mesh = 0
    for i, a in enumerate(argv):
        if a == "--mesh" and i + 1 < len(argv):
            mesh = int(argv[i + 1])
        elif a.startswith("--mesh="):
            mesh = int(a.split("=", 1)[1])
    if mesh > 1 and "xla_force_host_platform_device_count" not in \
            os.environ.get("XLA_FLAGS", ""):
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={mesh}"
        ).strip()


def make_requests(template, batch_size: int, n_requests: int, seed: int,
                  reserved=()):
    """Per-iteration request batches from a template batch.

    Integer fields (ids) are re-drawn uniformly over the template's
    observed [min, max] value range with the template's dtype and
    trailing shape — so every iteration dispatches a fresh id pattern
    against the same compiled program shape.  ``reserved`` ids (pad
    row 0, [MASK] for sequential heads) are excluded from the draw: a
    uniform draw that can emit the pad id asks the model about rows no
    real request contains, and a [MASK] hit corrupts the query-position
    protocol.  Float fields are row-SAMPLED from the template (the old
    tile path concatenated copies and truncated, so batch sizes that
    don't divide the template saw the same leading rows every
    iteration and never the tail).  Deterministic in ``seed``; yields
    ``n_requests`` dicts of numpy arrays with leading dim
    ``batch_size``.
    """
    rng = np.random.default_rng(seed)
    tmpl = {k: np.asarray(v) for k, v in template.items()}
    reserved = np.asarray(sorted({int(r) for r in reserved}), np.int64)
    for _ in range(n_requests):
        req = {}
        for name, v in tmpl.items():
            shape = (batch_size,) + v.shape[1:]
            if np.issubdtype(v.dtype, np.integer):
                lo, hi = int(v.min()), int(v.max())
                valid = np.arange(lo, hi + 1, dtype=np.int64)
                if reserved.size:
                    kept = np.setdiff1d(valid, reserved)
                    # keep the template's range if reserving would
                    # empty it (degenerate single-id fields)
                    valid = kept if kept.size else valid
                req[name] = valid[
                    rng.integers(0, valid.size, shape)].astype(v.dtype)
            else:
                rows = rng.integers(0, v.shape[0], batch_size)
                req[name] = v[rows]
        yield req


def _template_popularity(template, n_rows: int) -> np.ndarray:
    """Per-row id counts tallied from every integer field of the
    request template — the serving-side stand-in for train-set
    interaction counts when only the request stream is at hand."""
    counts = np.zeros(n_rows, np.int64)
    for v in template.values():
        v = np.asarray(v)
        if np.issubdtype(v.dtype, np.integer):
            ids = v.reshape(-1)
            ids = ids[(ids >= 0) & (ids < n_rows)]
            np.add.at(counts, ids, 1)
    return counts


def build_parser() -> argparse.ArgumentParser:
    """Batch-loop CLI: the retrieval flag cluster is the SHARED
    ``core.engine.add_spec_args`` set (identical flags to
    ``repro.launch.server``; identical flags resolve to identical
    specs via ``spec_from_args``)."""
    from repro.core import engine as engine_mod
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="two-tower-retrieval-jpq")
    ap.add_argument("--requests", type=int, default=20)
    ap.add_argument("--batch-size", type=int, default=64)
    ap.add_argument("--top-k", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    engine_mod.add_spec_args(ap)
    ap.add_argument("--mesh", type=int, default=0,
                    help="model-shard the catalogue S ways over host "
                         "devices (0 = no mesh)")
    ap.add_argument("--ckpt-dir", default=None)
    return ap


def main():
    _set_mesh_env(sys.argv[1:])
    args = build_parser().parse_args()

    import contextlib

    from repro.launch import compile_cache
    compile_cache.enable()
    import jax
    import jax.numpy as jnp
    from repro import dist
    from repro.configs import get_bundle
    from repro.core import engine as engine_mod
    from repro.core import serve as serve_mod
    from repro.nn import module as nn

    bundle = get_bundle(args.arch)
    model, batch, rng = bundle.make_smoke()
    params = model.init_params(rng)
    if args.ckpt_dir:
        from repro.ckpt import restore_checkpoint
        values, step = restore_checkpoint(args.ckpt_dir, nn.values(params))
        params = nn.with_values(params, values)
        print(f"restored step {step} from {args.ckpt_dir}")

    mesh_ctx = contextlib.nullcontext()
    if args.mesh > 1:
        from repro.launch.mesh import make_host_mesh
        mesh_ctx = dist.use_mesh_rules(
            make_host_mesh(args.mesh, model=args.mesh))

    template = {k: v for k, v in batch.items()
                if k not in ("label", "labels")}
    warm_state = None
    pruned = False
    engine_path = hasattr(model, "retrieve") \
        and hasattr(model, "bind_engine")
    if engine_path:
        spec = engine_mod.spec_from_args(args, kind=model.emb.cfg.kind,
                                         k=args.top_k)
        state = None
        if spec.prune and "item_emb" in params:
            # serving protocol (docs/serving.md): the presence mask is
            # codes-only — build the PruneState ONCE here, outside the
            # per-request jit, so the latency loop measures the bound
            # test and not an O(N·m) rebuild per request.  Under a mesh
            # the block size must tile the per-shard rows so the SAME
            # global state row-slices every request (permute-then-shard)
            from repro.core.assign import popularity_permutation
            codes = params["item_emb"]["codes"].value
            perm = None
            if spec.perm != "none":
                perm = popularity_permutation(
                    _template_popularity(template, codes.shape[0]))
            state = engine_mod.build_prune_state(
                codes, model.emb.cfg.b, shards=args.mesh, perm=perm)
            pruned = True
        elif spec.prune:
            import dataclasses
            spec = dataclasses.replace(spec, prune=False, perm="none",
                                       warm=None, stats=False)
        bound = model.bind_engine(params, spec)
        if pruned:
            bound.engine.bind_catalogue(prune=state)
        if pruned and spec.warm is not None:
            warm_state = serve_mod.ThresholdState(spec.warm)
            fn = jax.jit(lambda b, w: bound.retrieve(b, floor=w))
        else:
            fn = jax.jit(lambda b: bound.retrieve(b))
    else:
        fn = jax.jit(model.serve)

    def dispatch(req):
        req = {k: jnp.asarray(v) for k, v in req.items()}
        if not engine_path:
            out = fn(params, req)
        elif warm_state is not None:
            out = fn(req, jnp.asarray(warm_state.floor(args.batch_size)))
        else:
            out = fn(req)
        jax.block_until_ready(out)
        return out

    def account(out):
        # OUTSIDE the timed window: device->host stats readback + EMA
        # update are instrumentation, not serve latency
        if not pruned:
            return
        nonlocal skipped, total
        *_, stats = out
        if warm_state is not None:
            warm_state.update(np.asarray(stats["theta"]))
        skipped += float(stats["skipped_tiles"])
        total += float(stats["total_tiles"])

    # retrieval archs speak 1-based item ids: row 0 is padding, and
    # sequential heads reserve the [MASK] row — neither belongs in a
    # synthetic request stream
    reserved = ()
    if hasattr(model, "retrieve") or hasattr(model, "retrieve_topk"):
        reserved = (0,)
        cfg = getattr(model, "cfg", None)
        if cfg is not None and hasattr(cfg, "mask_id"):
            reserved = (0, int(cfg.mask_id))
    reqs = make_requests(template, args.batch_size, args.requests + 1,
                         args.seed, reserved=reserved)
    lats, skipped, total = [], 0.0, 0.0
    with mesh_ctx:
        account(dispatch(next(reqs)))              # compile
        for req in reqs:
            t0 = time.perf_counter()
            out = dispatch(req)
            lats.append((time.perf_counter() - t0) * 1e3)
            account(out)
    lats = np.asarray(lats)
    mode = ("fused" if args.fused else "materialise") \
        if engine_path else "serve"
    if engine_path and spec.kind == "semantic":
        # generative head: constrained beam decode over the codebooks
        mode = "semantic" + ("" if spec.beams is None
                             else f"@{spec.beams}")
    # label what actually ran: `pruned` is only set when the arch's
    # embedding is JPQ and the fused path took the PruneState — argv
    # alone would claim pruning for archs that fell through to the
    # reference path
    if pruned:
        mode = "fused+prune"
        if args.perm:
            mode += "+perm"
        if warm_state is not None:
            mode += "+warm"
    extra = ""
    if pruned and total > 0:
        # aggregated across ALL shards by fused_topk_over_codes' stats
        # (mean weighted by local tile count), then across requests
        extra = f" skip={skipped / total:.3f}"
    if args.mesh > 1:
        extra += f" mesh={args.mesh}"
    print(f"{args.arch}: batch={args.batch_size} n={args.requests} "
          f"path={mode} seed={args.seed} "
          f"p50={np.percentile(lats, 50):.2f}ms "
          f"p99={np.percentile(lats, 99):.2f}ms{extra}")


if __name__ == "__main__":
    main()

#!/usr/bin/env python3
"""Readings that the correctness limits and the serving rates are set
from.  Not part of a benchmark run.

    python3 benchmarks/chip/calibrate.py readings --workload <cell> \
        --seeds 1,2,3 [--seconds 3]
    python3 benchmarks/chip/calibrate.py sweep --workload <cell> \
        --rates 500,1000,2000 [--seconds 5] [--seed 1]
    python3 benchmarks/chip/calibrate.py padding \
        --config <config> --traffic <serve traffic> --seeds 1,2,3

``readings`` prints, per seed, the numbers compared for the program, for
the control (the plain reference with float8 e4m3 matrix inputs, the
next precision below the configuration's bfloat16 inputs), for the
reference at bfloat16 inputs (a witness of what the configuration's own
precision reads), and for a training cell the program with half of each
batch left out.  A serving cell first runs a window of ``--seconds`` at
its own load.  All seeds run in one process.

``sweep`` serves one cell at each rate for ``--seconds`` and prints the
share of due requests answered within the window, the queue depth at
each whole second and the latency percentiles: the knee is the highest
rate whose backlog does not grow.

``padding`` serves a window of a serving mix twice per seed: once as
the server pads histories (``Batch.padded_hist``), once with the
histories left-padded as the program trains, and prints the number
compared for each, against the reference (which scores from the last
item of a left-padded history) and, for the server's own padding, also
against the reference on the server's padded input.  ``--config`` and
``--traffic`` name a configuration and a mix that need not form a cell
of ``BENCHMARK.json``; any mode takes them in place of ``--workload``.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

import numpy as np  # noqa: E402

from chip import harness  # noqa: E402


def _cell(files, seed, spans, **kw):
    import importlib
    driver = importlib.import_module(
        f"chip.drivers.{files['traffic']['driver']}")
    return driver.Cell(config=files["config"], traffic=files["traffic"],
                       seed=seed, spans=spans, **kw)


def readings(files, seeds, seconds, out):
    for seed in seeds:
        t = time.perf_counter()
        spans = harness.Spans()
        row = {"seed": seed}
        c = _cell(files, seed, spans)
        c.setup()
        if files["traffic"]["driver"] == "train":
            prog = c.program_readings()
            c.free()
            want = c.reference("f32")
            row["program"] = c.compare(prog, want)
            row["control_fp8"] = c.compare(c.reference("fp8"), want)
            row["witness_bf16"] = c.compare(c.reference("bf16"), want)
            f = _cell(files, seed, spans, fault="half_batch")
            f.setup()
            row["fault_half_batch"] = f.compare(f.program_readings(), want)
            f.free()
        else:
            c.prepare(seconds)
            c.window(seconds, time.perf_counter)
            c.free()
            idx = c.sample()
            want = c.reference_scores(idx, "f32")
            row["program"] = {"score_gap": c.gap(c.served(idx), want)}
            row["control_fp8"] = {"score_gap": c.gap(
                c.control_served(idx, "fp8"), want)}
            row["witness_bf16"] = {"score_gap": c.gap(
                c.control_served(idx, "bf16"), want)}
            row["answered"] = int(c.attempted() - c.failed)
        row["seconds"] = time.perf_counter() - t
        line = json.dumps(row)
        print(line, flush=True)
        out.write(line + "\n")
        out.flush()


@contextlib.contextmanager
def left_padded_batches():
    """The server's batches padded as the program trains: pad first,
    then each history's most recent items."""
    from repro.serve import queue
    own = queue.Batch.padded_hist

    def padded_hist(self):
        out = np.full((self.max_batch, self.bucket_len), queue.PAD_ID,
                      np.int32)
        for i, r in enumerate(self.requests):
            h = r.hist[-self.bucket_len:]
            out[i, self.bucket_len - h.size:] = h
        return out

    queue.Batch.padded_hist = padded_hist
    try:
        yield
    finally:
        queue.Batch.padded_hist = own


def _served_gap(files, seed, seconds, spans, right_ref=False):
    """(gap against the reference, gap against the reference on the
    server's right-padded input or None) of one window at ``seed``."""
    import jax
    import jax.numpy as jnp
    from chip.drivers import serve
    c = _cell(files, seed, spans)
    c.setup()
    c.prepare(seconds)
    c.window(seconds, time.perf_counter)
    c.free()
    idx = c.sample()
    served = c.served(idx)
    gap = c.gap(served, c.reference_scores(idx, "f32"))
    if not right_ref:
        return gap, None
    v = jax.tree.map(jnp.asarray, c.values)
    want = np.zeros((len(idx), c.config["n_items"] + 2), np.float32)
    for r, j in enumerate(idx):
        h = c.hists[j]
        L = serve.bucket_of(c.buckets, h.size)
        seq = np.zeros((1, L), np.int32)
        seq[0, :min(h.size, L)] = h[-L:]
        want[r] = np.asarray(serve._ref_scores(
            c.ref, v, jnp.asarray(seq), c.config["n_heads"], "f32"))[0]
    return gap, c.gap(served, want)


def padding(files, seeds, seconds, out):
    for seed in seeds:
        t = time.perf_counter()
        spans = harness.Spans()
        own, own_right = _served_gap(files, seed, seconds, spans, True)
        with left_padded_batches():
            left, _ = _served_gap(files, seed, seconds, spans)
        row = {"seed": seed, "server_padding": {"score_gap": own},
               "server_padding_vs_right_padded_reference":
                   {"score_gap": own_right},
               "left_padded": {"score_gap": left},
               "seconds": time.perf_counter() - t}
        line = json.dumps(row)
        print(line, flush=True)
        out.write(line + "\n")
        out.flush()


def sweep(files, seed, rates, seconds, out):
    c = _cell(files, seed, harness.Spans())
    c.setup()
    for rate in rates:
        c.rate = rate
        c.prepare(seconds)
        c.window(seconds, time.perf_counter)
        lat = c.latency_ms()
        row = {"rate": rate, "due": int(c.due.size),
               "answered_in_window": c.in_window,
               "answered_share": c.in_window / max(c.due.size, 1),
               "depth_each_s": c.depths,
               "p50_ms": float(np.percentile(lat, 50)),
               "p95_ms": float(np.percentile(lat, 95)),
               "late_p50_ms": float(np.percentile(c.late, 50) * 1e3)}
        line = json.dumps(row)
        print(line, flush=True)
        out.write(line + "\n")
        out.flush()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("mode", choices=("readings", "sweep", "padding"))
    ap.add_argument("--workload")
    ap.add_argument("--config")
    ap.add_argument("--traffic")
    ap.add_argument("--seeds", default="1")
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--rates", default="")
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--out", default="chiprun_out/calibrate")
    args = ap.parse_args(argv)
    harness.configure_jax_env()
    if args.workload:
        files, name = harness.load_cell(args.workload), args.workload
    else:
        files = {"cell": {"chips": 1},
                 "config": harness.load_json(harness.HERE, "configs",
                                             args.config + ".json"),
                 "traffic": harness.load_json(harness.HERE, "traffic",
                                              args.traffic + ".json")}
        name = f"{args.config}.{args.traffic}"
    harness.require_devices(int(files["cell"]["chips"]))
    os.makedirs(args.out, exist_ok=True)
    path = os.path.join(args.out, f"{args.mode}-{name}.jsonl")
    seeds = [int(s) for s in args.seeds.split(",")]
    with open(path, "a") as out:
        if args.mode == "readings":
            readings(files, seeds, args.seconds, out)
        elif args.mode == "padding":
            padding(files, seeds, args.seconds, out)
        else:
            sweep(files, args.seed, [float(r) for r in
                                     args.rates.split(",")],
                  args.seconds, out)
    return 0


if __name__ == "__main__":
    sys.exit(main())

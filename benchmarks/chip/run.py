#!/usr/bin/env python3
"""Run one benchmark cell once, on the chips of this machine.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

Everything about the cell is found by name (``chip/harness.py`` says
where).  Set-up (data, codes, weights, compilation, warm-up, the first
steps) counts as ``setup_s``; then the cell's driver runs for
``--seconds``; then the timed path's output is compared with the plain
reference.  ``--trace 0`` reports the cell's end-to-end metrics,
``--trace 1`` runs the window under the profiler and reports its
per-layer metrics.  The last line on standard output is one JSON
object; each number compared is printed beside its limit as the last
lines on standard error and under ``checks`` in that object.

Exits non-zero, printing no result, where JAX finds no accelerator or
fewer chips than the cell asks for.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

from chip import harness, trace  # noqa: E402


def run_cell(name: str, seed: int, seconds: float, traced: bool, *,
             root: str = harness.ROOT, require_chip: bool = True,
             fault=None, cell_files=None) -> dict:
    """One run of cell ``name``; returns (result, checks).  The tests
    pass ``require_chip=False``, small ``cell_files`` and a ``fault``."""
    files = cell_files or harness.load_cell(name, root)
    cell, config, traffic = files["cell"], files["config"], files["traffic"]
    chips = int(cell["chips"])
    import jax
    devices = (harness.require_devices(chips) if require_chip
               else jax.devices()[:chips])
    clock = time.perf_counter
    spans = harness.Spans(clock)
    compiles = harness.CompileClock()
    driver = importlib.import_module(f"chip.drivers.{traffic['driver']}")
    c = driver.Cell(config=config, traffic=traffic, seed=seed, spans=spans,
                    fault=fault)
    c.setup()
    if hasattr(c, "prepare"):
        c.prepare(seconds)
    setup_s = clock() - T_START
    # off the chip (the harness's own tests) the readers get v5e peaks
    kind = devices[0].device_kind if require_chip else "TPU v5 lite"
    run = harness.Run(name=name, config=config, traffic=traffic,
                      chips=chips, peaks=harness.peaks(kind),
                      seconds=seconds)

    def window():
        before = compiles.count
        with spans.span(trace.WINDOW_SPAN):
            out = c.window(seconds, clock)
        run.counters["window_compiles"] = compiles.count - before
        return out

    if traced:
        (t0, t1), ev = trace.capture(window, os.path.join(
            root, ".bench_traces", name))
        run.trace = trace.Reduced(ev)
    else:
        t0, t1 = window()
    memory = harness.memory_peak_bytes(devices)
    run.window = (t0, t1)
    run.e2e = c.end_to_end(t0, t1)
    run.e2e["setup_s"] = setup_s
    run.counters.update(c.counters())
    c.free()
    t_check = clock()
    numbers = c.check()
    print(f"check_s={clock() - t_check}", file=sys.stderr, flush=True)
    limits = files["limits"]
    checks = [(k, float(v), float(limits[k])) for k, v in numbers.items()]
    correct = all(v <= lim for _, v, lim in checks) and c.failed == 0
    device = harness.device_info(devices, memory)
    result = {"correct": bool(correct), "attempted": int(c.attempted()),
              "failed": int(c.failed)}
    if traced:
        result["metrics"] = harness.read_per_layer(run, files["per_layer"])
        device["busy_s"] = run.trace.busy_s()
        device["window_s"] = run.trace.window_s
        result["device"] = device
        result["breakdown"] = {"device_ops": run.trace.top_ops(10),
                               "idle_gaps": run.trace.idle_gaps(10)}
    else:
        result["metrics"] = {
            m["name"]: {"value": harness.finite(float(run.e2e[m["name"]])),
                        "unit": m["unit"]}
            for m in files["end_to_end"]}
        result["device"] = device
    phases = {n[len("bench.setup_"):]: round(b - a, 3)
              for n, a, b in spans.records if n.startswith("bench.setup_")}
    print(f"setup_s={setup_s} phases={phases}", file=sys.stderr, flush=True)
    print("counters " + " ".join(f"{k}={v}" for k, v in run.counters.items()
                                 if not isinstance(v, list)),
          file=sys.stderr, flush=True)
    return result, checks


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    harness.configure_jax_env()
    try:
        result, checks = run_cell(args.workload, args.seed, args.seconds,
                                  bool(args.trace))
    except harness.NoAccelerator as e:
        print(f"run.py: {e}", file=sys.stderr)
        return 2
    harness.emit(result, checks)
    return 0


if __name__ == "__main__":
    sys.exit(main())

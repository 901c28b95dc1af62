"""What every cell shares: finding a cell's files by name, the device
check, the compile cache, host spans, the per-layer metric readers and
the result line.

How the harness finds things (all by the names in ``BENCHMARK.json``):

  cell            the ``workloads`` entry of that name
  configuration   ``configs/<config>.json``; its ``reference`` names the
                  plain reference module ``reference/<reference>.py``
  traffic mix     ``traffic/<traffic>.json``; its ``driver`` names the
                  driver module ``drivers/<driver>.py``
  limits          ``workloads/<cell>.json``: the correctness limits of
                  the cell and the readings they were set from
  per-layer       ``metrics/<metric>.py``, whose ``read(run)`` returns a
  metric          number, or None where the run has nothing to read
"""
from __future__ import annotations

import contextlib
import importlib
import json
import os
import sys
import time
from typing import Callable, Dict, List

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
CACHE_DIR = os.path.join(ROOT, ".jax_cache")


class NoAccelerator(SystemExit):
    """JAX finds no accelerator, or fewer chips than the cell asks for."""


def load_json(*parts) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_cell(name: str, root: str = ROOT) -> dict:
    """Everything a run of cell ``name`` reads, by name."""
    bench = load_json(root, "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json; "
                         f"cells: {sorted(cells)}")
    cell = cells[name]
    here = os.path.join(root, "benchmarks", "chip")
    return {
        "cell": cell,
        "config": load_json(here, "configs", cell["config"] + ".json"),
        "traffic": load_json(here, "traffic", cell["traffic"] + ".json"),
        "limits": load_json(here, "workloads", name + ".json")["limits"],
        "end_to_end": [m for m in bench["end_to_end"]
                       if name in m.get("workloads", [name])],
        "per_layer": [m for m in bench["per_layer"]
                      if name in m.get("workloads", [name])],
    }


def reference(config: dict):
    """The configuration's plain reference module, by name."""
    return importlib.import_module(f"chip.reference.{config['reference']}")


def configure_jax_env() -> None:
    """Before JAX is imported: the persistent compilation cache in the
    checkout, whatever the environment says, and every program cached.
    Nothing is evicted: with a size limit set, JAX's cache fails every
    write once it holds an entry written without one (no access-time
    file), and each run then compiles anew."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    os.environ["JAX_COMPILATION_CACHE_MAX_SIZE"] = "-1"
    os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    os.environ["JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES"] = "0"
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    src = os.path.join(ROOT, "src")
    if src not in sys.path:
        sys.path.insert(0, src)


def require_devices(chips: int):
    """The first ``chips`` accelerator devices; raises NoAccelerator
    where JAX finds none, or too few.  Never falls back to the CPU."""
    import jax
    devs = jax.devices()
    if devs[0].platform == "cpu":
        raise NoAccelerator(f"JAX finds no accelerator (platform "
                            f"{devs[0].platform!r}); nothing was run")
    if len(devs) < chips:
        raise NoAccelerator(f"the cell needs {chips} chips, JAX finds "
                            f"{len(devs)}")
    return devs[:chips]


def peaks(device_kind: str) -> dict:
    """The device's published peaks; a kind missing from the table is
    an error."""
    table = load_json(HERE, "peaks.json")["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"peaks.json; known: {sorted(table)}")
    return table[device_kind]


def memory_peak_bytes(devices) -> int:
    """The largest device-memory peak over ``devices``: the allocator's
    reservation, which on TPU includes program temporaries, or its peak
    of live buffers where that is larger."""
    best = 0
    for d in devices:
        st = d.memory_stats() or {}
        best = max(best, int(st.get("peak_bytes_reserved", 0)),
                   int(st.get("peak_bytes_in_use", 0)))
    return best


class Spans:
    """Host spans of the benchmark's own, written into the profiler's
    trace as well (``TraceAnnotation``) when one is running."""

    def __init__(self, clock: Callable[[], float] = time.perf_counter):
        self.clock = clock
        self.records: List[tuple] = []

    @contextlib.contextmanager
    def span(self, name: str):
        import jax
        t0 = self.clock()
        with jax.profiler.TraceAnnotation(name):
            yield
        self.records.append((name, t0, self.clock()))


class CompileClock:
    """Counts the backend compilations JAX reports (after
    ``chip_smoke.CompileClock``, which sums their seconds)."""

    def __init__(self):
        import jax
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.count += 1


class Run:
    """What a run hands the per-layer metric readers."""

    def __init__(self, *, name: str, config: dict, traffic: dict,
                 chips: int, peaks: dict, seconds: float):
        self.name = name
        self.config = config
        self.traffic = traffic
        self.chips = chips
        self.peaks = peaks
        self.seconds = seconds
        self.e2e: Dict[str, float] = {}
        self.counters: Dict[str, float] = {}
        self.trace = None              # chip.trace.Reduced, traced runs
        self.window = (0.0, 0.0)       # host clock, seconds


def read_per_layer(run: Run, metrics: List[dict]) -> Dict[str, dict]:
    """Each per-layer metric's reader, found by name; a reader that
    finds nothing to read leaves its metric out."""
    out = {}
    for m in metrics:
        mod = importlib.import_module(f"chip.metrics.{m['name']}")
        v = mod.read(run)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out


def device_info(devices, memory_peak: int) -> dict:
    d = devices[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices), "memory_peak_bytes": int(memory_peak)}


def finite(x: float) -> float:
    """A number JSON can carry: infinities become +-1e300."""
    return x if abs(x) < float("inf") else (1e300 if x > 0 else -1e300)


def emit(result: dict, checks: List[tuple]) -> None:
    """Print each compared number beside its limit as the last lines on
    standard error, and the result as the last line on standard
    output, with the comparisons under ``checks``, last."""
    result = dict(result)
    result["checks"] = {n: {"value": finite(v), "limit": lim}
                        for n, v, lim in checks}
    for n, v, lim in checks:
        print(f"check {n}: {v!r} (limit {lim!r})", file=sys.stderr,
              flush=True)
    print(json.dumps(result), flush=True)

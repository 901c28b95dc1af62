"""Tiny cells for the harness's tests on the CPU: the shapes of the
real configurations cut far down, the same drivers and checks."""
from __future__ import annotations

import copy

from chip import harness

CONFIG = {
    "name": "tiny", "reference": "sasrec_jpq", "arch": "sasrec",
    "n_items": 300, "max_len": 16, "d_model": 256, "n_layers": 2,
    "n_heads": 4, "d_ff": 512, "m": 8, "b": 16, "loss": "full_ce",
    "data": {"n_users": 400, "n_clusters": 5, "zipf_a": 1.2,
             "stay_prob": 0.85, "min_len": 6, "max_len": 16},
}
TRAIN = {"driver": "train", "batch": 8, "lr": 1e-3}
SERVE = {"driver": "serve", "rate": 200, "k": 10, "min_len": 3,
         "max_len": 16, "buckets": [8, 16], "max_batch": 4,
         "max_delay_ms": 5.0, "prune": True, "perm": True}


# the serving metrics, which no cell of BENCHMARK.json reports yet
SERVE_E2E = [{"name": "serve_p50_ms", "unit": "ms"},
             {"name": "serve_done_per_s", "unit": "req/s"}]


def cell_files(kind: str, limits: dict) -> dict:
    bench = harness.load_json(harness.ROOT, "BENCHMARK.json")
    setup = [m for m in bench["end_to_end"] if m["name"] == "setup_s"]
    e2e = [m for m in bench["end_to_end"]
           if m["name"] == "train_seq_per_s"] if kind == "train" \
        else copy.deepcopy(SERVE_E2E)
    return {
        "cell": {"name": f"tiny-{kind}", "chips": 1},
        "config": copy.deepcopy(CONFIG),
        "traffic": copy.deepcopy(TRAIN if kind == "train" else SERVE),
        "limits": limits,
        "end_to_end": e2e + setup,
        "per_layer": [],
    }

"""Mean real rows per dispatched batch over ``max_batch``, from the
program's ``ServerMetrics``."""
from __future__ import annotations


def read(run):
    occ = run.counters.get("batch_occupancy")
    if not run.counters.get("batches"):
        return None
    return 100.0 * occ

"""Tiles the pruned PQTopK sweep skipped over the tiles it had, summed
from the stats the pruned path returns (``ServerMetrics``)."""
from __future__ import annotations


def read(run):
    frac = run.counters.get("skip_fraction")
    return None if frac is None else 100.0 * frac

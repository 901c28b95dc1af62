"""Share of the traced serving window in which no operation ran on
the device (averaged over the chips)."""
from __future__ import annotations


def read(run):
    if run.trace is None or not run.trace.ops:
        return None
    return 100.0 * run.trace.idle_share()

"""One served batch's share of the chip's bf16 peak: the model FLOPs of
the real requests dispatched in the traced window, over the host wall
time of those dispatches (the benchmark's span around
``pool.serve``), over the peak.

Per real request: the encoder at its bucket length (every position is
computed), one partial-score table, and m adds for each item of the
tiles the sweep did not skip."""
from __future__ import annotations

from chip import flops


def read(run):
    batches = [b for b in run.counters.get("batch_log", [])
               if run.window[0] <= b[0] and b[1] <= run.window[1]]
    if not batches or run.trace is None:
        return None
    c = run.config
    work, wall = 0.0, 0.0
    for t0, t1, n_real, max_batch, L, skipped, total in batches:
        swept = (total - skipped) * run.counters["block_n"]
        per = L * flops.encoder_position(c, L) + flops.lut(c) \
            + c["m"] * swept
        work += n_real * per
        wall += t1 - t0
    return 100.0 * work / wall / run.peaks["bf16_flops"]

"""Training step's share of the chip's bf16 peak: model FLOPs per
sequence (``flops.train_sequence``) times the sequences the traced
window completed per second, over chips and the peak."""
from __future__ import annotations

from chip import flops


def read(run):
    rate = run.e2e.get("train_seq_per_s")
    if not rate:
        return None
    return (100.0 * flops.train_sequence(run.config) * rate
            / run.chips / run.peaks["bf16_flops"])

"""The pruned PQTopK kernel's share of its roofline: the least time the
chip needs for the algorithm's work on the tiles actually swept (the
larger of bytes over HBM bandwidth and operations over the bf16 peak),
over the device time of the kernel's events in the traced window.

Bytes: the codes of every swept tile at one byte per code (the design
point of ``core/jpq.py``), the partial-score table once per batch, and
the [B, k] values and ids.  Operations: B x swept items x m adds.  The
one-hot matrix products the kernel uses to pick each split are not
counted: they are the implementation's, not the algorithm's."""
from __future__ import annotations

KERNEL = "jpq_topk_pruned"


def work(c: dict, k: int, max_batch: int, swept_items: float):
    """(bytes, operations) of one pruned sweep over ``swept_items``."""
    nbytes = (swept_items * c["m"] + max_batch * c["m"] * c["b"] * 4
              + max_batch * k * 8)
    return nbytes, max_batch * swept_items * c["m"]


def read(run):
    if run.trace is None:
        return None
    kernel_s = run.trace.op_seconds(lambda n: KERNEL in n)
    batches = [b for b in run.counters.get("batch_log", [])
               if run.window[0] <= b[0] and b[1] <= run.window[1]]
    if kernel_s <= 0 or not batches:
        return None
    least = 0.0
    for _, _, _, max_batch, _, skipped, total in batches:
        swept = (total - skipped) * run.counters["block_n"]
        nbytes, ops = work(run.config, run.traffic["k"], max_batch, swept)
        least += max(nbytes / run.peaks["hbm_bytes_per_s"],
                     ops / run.peaks["bf16_flops"])
    return 100.0 * least / kernel_s

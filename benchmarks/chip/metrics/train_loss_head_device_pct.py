"""Device self time of the loss head (the program's
``seqrec.loss_head`` scope: masking, cross-entropy and the masked
mean, both directions) as a share of the traced training window,
averaged over the chips."""
from __future__ import annotations

from chip import scopes


def read(run):
    return scopes.pct(run, "seqrec.loss_head")

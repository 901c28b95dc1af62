"""Device self time of the ops on none of the training step's
scopes (``scopes.SCOPES``) as a share of the traced training window,
averaged over the chips.  Read only where the program carries the
scopes."""
from __future__ import annotations

from chip import scopes


def read(run):
    return scopes.pct(run, None)

"""Device self time of the sequence encoder (the program's
``seqrec.encode`` scope, both directions) as a share of the traced
training window, averaged over the chips."""
from __future__ import annotations

from chip import scopes


def read(run):
    return scopes.pct(run, "seqrec.encode")

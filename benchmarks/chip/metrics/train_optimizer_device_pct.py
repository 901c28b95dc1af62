"""Device self time of the optimizer update (the program's
``train.optimizer`` scope) as a share of the traced training window,
averaged over the chips."""
from __future__ import annotations

from chip import scopes


def read(run):
    return scopes.pct(run, "train.optimizer")

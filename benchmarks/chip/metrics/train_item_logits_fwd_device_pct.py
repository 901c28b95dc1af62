"""Device self time of the item logits' forward (ops under the
program's ``item_emb.logits`` scope and not under ``transpose(``) as a
share of the traced training window, averaged over the chips."""
from __future__ import annotations

from chip import scopes


def read(run):
    return scopes.pct(run, "item_emb.logits", backward=False)

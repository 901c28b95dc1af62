"""Device self time of the item logits' backward (ops under the
program's ``item_emb.logits`` scope and under ``transpose(``: the
scatter-add into the centroids) as a share of the traced training
window, averaged over the chips."""
from __future__ import annotations

from chip import scopes


def read(run):
    return scopes.pct(run, "item_emb.logits", backward=True)

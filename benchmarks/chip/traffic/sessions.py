"""Seeded session generator: the benchmark's own, vectorised copy of
``repro.data.sequences.SyntheticSequences``.

Items belong to ``n_clusters`` latent clusters.  Popularity is Zipf
(exponent ``zipf_a``) over a seeded permutation of the catalogue, and a
session is a random walk over clusters: at each step it stays in its
cluster with probability ``stay_prob``, else jumps to a uniformly drawn
one, and then draws an item of the current cluster in proportion to its
popularity.  Items are 1-based (0 is padding).

Unlike the program's per-draw Python loop this draws every session at
once with NumPy, so a million-item catalogue takes seconds.  The draws
differ from the program's stream; the distribution is the same.
"""
from __future__ import annotations

import numpy as np


class Catalogue:
    """Cluster membership and popularity of ``n_items`` items."""

    def __init__(self, n_items: int, *, n_clusters: int, zipf_a: float,
                 rng: np.random.Generator):
        self.n_items = int(n_items)
        self.n_clusters = int(n_clusters)
        cluster = rng.integers(0, n_clusters, self.n_items)
        pop = 1.0 / np.arange(1, self.n_items + 1) ** zipf_a
        self.pop = pop[rng.permutation(self.n_items)]
        # items grouped by cluster; one cumulative distribution per
        # cluster, offset by the cluster's index so that a single
        # searchsorted over the concatenation draws from any cluster
        order = np.argsort(cluster, kind="stable")
        self.items_by_cluster = order
        counts = np.bincount(cluster, minlength=n_clusters)
        self.start = np.concatenate([[0], np.cumsum(counts)])
        w = self.pop[order]
        cdf = np.empty(self.n_items, np.float64)
        for k in range(n_clusters):
            a, b = self.start[k], self.start[k + 1]
            if b > a:
                c = np.cumsum(w[a:b])
                cdf[a:b] = k + c / c[-1]
        self.cdf = cdf
        self.nonempty = np.flatnonzero(counts > 0)

    def draw(self, clusters: np.ndarray,
             rng: np.random.Generator) -> np.ndarray:
        """One item (1-based) per entry of ``clusters``."""
        u = clusters + rng.random(clusters.shape) * (1.0 - 1e-12)
        pos = np.searchsorted(self.cdf, u, side="right")
        # stay inside the cluster against rounding at its top end
        pos = np.minimum(pos, self.start[clusters + 1] - 1)
        return self.items_by_cluster[pos] + 1


def sessions(cat: Catalogue, n: int, *, min_len: int, max_len: int,
             stay_prob: float, rng: np.random.Generator, lengths=None):
    """``n`` sessions as (items [n, max_len] int64 right-filled with 0,
    lengths [n]).  Row i holds its ``lengths[i]`` items first; the
    lengths are drawn unless given."""
    if lengths is None:
        lengths = rng.integers(min_len, max_len + 1, n)
    jump = rng.random((n, max_len)) > stay_prob
    jump[:, 0] = True
    fresh = cat.nonempty[rng.integers(0, cat.nonempty.size, (n, max_len))]
    # forward-fill each row's cluster from its last jump
    idx = np.where(jump, np.arange(max_len)[None, :], 0)
    np.maximum.accumulate(idx, axis=1, out=idx)
    clusters = np.take_along_axis(fresh, idx, axis=1)
    items = cat.draw(clusters, rng)
    items[np.arange(max_len)[None, :] >= lengths[:, None]] = 0
    return items, lengths


def corpus(n_items: int, params: dict, seed: int):
    """The seeded corpus of a configuration: (catalogue, items, lengths)
    for ``params['n_users']`` users with sessions of
    ``params['min_len']``..``params['max_len']`` items."""
    rng = np.random.default_rng([int(seed), 0])
    cat = Catalogue(n_items, n_clusters=params["n_clusters"],
                    zipf_a=params["zipf_a"], rng=rng)
    items, lengths = sessions(cat, params["n_users"],
                              min_len=params["min_len"],
                              max_len=params["max_len"],
                              stay_prob=params["stay_prob"], rng=rng)
    return cat, items, lengths


def interactions(items: np.ndarray, lengths: np.ndarray):
    """(users, item_rows 0-based) of every session item but the last
    two, which the program's data protocol holds out."""
    keep = np.arange(items.shape[1])[None, :] < (lengths - 2)[:, None]
    users = np.nonzero(keep)[0]
    return users, items[keep] - 1


def left_pad(items: np.ndarray, lengths: np.ndarray, L: int) -> np.ndarray:
    """Right-filled rows -> [n, L] rows holding each row's last ``L``
    items at the right end, 0 before them (the training protocol)."""
    n, W = items.shape
    out = np.zeros((n, L), items.dtype)
    # column c of the output holds item (length - L + c) of the row
    src = lengths[:, None] - L + np.arange(L)[None, :]
    ok = (src >= 0) & (src < W)
    vals = np.take_along_axis(items, np.clip(src, 0, W - 1), axis=1)
    out[ok] = vals[ok]
    return out

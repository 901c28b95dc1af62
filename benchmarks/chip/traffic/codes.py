"""RecJPQ code assignment by truncated SVD: the benchmark's own copy of
``repro.core.assign``'s ``svd`` strategy (Halko randomized SVD of the
binary user-item matrix, then per-component min-max normalisation,
N(0, 1e-5) tie-breaking noise and ``b`` equal-mass quantile bins).

The copy multiplies by the interaction matrix through SciPy's sparse
products instead of ``np.add.at``, which is what makes a million-item
catalogue take seconds.
"""
from __future__ import annotations

import numpy as np
import scipy.sparse as sp


def svd_codes(users: np.ndarray, items: np.ndarray, n_users: int,
              n_items: int, m: int, b: int, *, seed: int,
              oversample: int = 8, n_iter: int = 2) -> np.ndarray:
    """uint8/int32 codes [n_items, m] with entries in [0, b)."""
    rng = np.random.default_rng([int(seed), 1])
    a = sp.coo_matrix((np.ones(users.size, np.float64), (users, items)),
                      shape=(n_users, n_items)).tocsr()
    a.data[:] = 1.0                          # binary: duplicates count once
    at = a.T.tocsr()
    k = min(m + oversample, n_users, n_items)
    y = a @ rng.standard_normal((n_items, k))
    for _ in range(n_iter):
        y, _ = np.linalg.qr(y)
        z, _ = np.linalg.qr(at @ y)
        y = a @ z
    q, _ = np.linalg.qr(y)
    _, _, vt = np.linalg.svd((at @ q).T, full_matrices=False)
    emb = vt[:m].T
    if emb.shape[1] < m:
        emb = np.concatenate(
            [emb, 1e-3 * rng.standard_normal((n_items, m - emb.shape[1]))],
            1)
    lo, hi = emb.min(0, keepdims=True), emb.max(0, keepdims=True)
    span = np.where(hi - lo > 0, hi - lo, 1.0)
    norm = (emb - lo) / span + rng.normal(0.0, 1e-5, emb.shape)
    codes = np.empty(emb.shape, np.int32)
    for j in range(m):
        qs = np.quantile(norm[:, j], np.linspace(0, 1, b + 1)[1:-1])
        codes[:, j] = np.searchsorted(qs, norm[:, j], side="right")
    codes = np.clip(codes, 0, b - 1)
    return codes.astype(np.uint8 if b <= 256 else np.int32)

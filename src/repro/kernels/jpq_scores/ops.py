"""jit'd public wrapper for the jpq_scores kernel.

Handles arbitrary leading batch dims, pads B/N to block multiples, and
falls back to interpret mode off-TPU so the same call site works on CPU
tests and TPU production.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.jpq_scores.jpq_scores import jpq_scores_lut


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def jpq_scores(h, centroids, codes, *, block_b: int = 256,
               block_n: int = 512, interpret: bool | None = None):
    """h [..., d], centroids [m, b, dk], codes [N, m] -> [..., N] fp32."""
    if interpret is None:
        interpret = not _on_tpu()
    m, b, dk = centroids.shape
    lead = h.shape[:-1]
    B = 1
    for s in lead:
        B *= s
    h2 = h.reshape(B, m, dk).astype(jnp.float32)
    partial = jnp.einsum("bmk,mck->bmc", h2, centroids.astype(jnp.float32))
    N = codes.shape[0]
    bb = min(block_b, _ceil_mult(B, 8))
    bn = min(block_n, _ceil_mult(N, 128))
    Bp, Np = _ceil_mult(B, bb), _ceil_mult(N, bn)
    partial = jnp.pad(partial, ((0, Bp - B), (0, 0), (0, 0)))
    codes_p = jnp.pad(codes, ((0, Np - N), (0, 0)))
    out = jpq_scores_lut(partial, codes_p, block_b=bb, block_n=bn,
                         interpret=interpret)
    return out[:B, :N].reshape(*lead, N)


def _ceil_mult(x: int, m: int) -> int:
    return (x + m - 1) // m * m

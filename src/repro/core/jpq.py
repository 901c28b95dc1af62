"""RecJPQ embedding: codebook of sub-item centroid ids + centroid tensor.

The embedding tensor ``[n_items, d]`` is replaced by
  codes      int32 [n_items, m]   (frozen; built by repro.core.assign)
  centroids  float [m, b, d//m]   (trainable)
Item i's embedding = concat_j centroids[j, codes[i, j]]  (paper Fig. 2).

Two hot paths:
  lookup(ids)  - input-side reconstruction (sequence of ids -> vectors)
  logits(h)    - score *every* item for hidden state(s) h via the
                 partial-score trick: P[j,c] = <h_j, centroids[j,c]>
                 then scores_i = sum_j P[j, codes[i,j]].
                 HBM traffic = m bytes/item instead of 4d bytes/item.
The Pallas TPU kernel for logits lives in repro/kernels/jpq_scores.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro import dist
from repro.nn import module as nn
from repro.nn.module import P, KeyGen


def init(kg: KeyGen, n_items: int, d: int, m: int, b: int = 256, *,
         codes=None, dtype=jnp.float32, init_scale: float | None = None):
    assert d % m == 0, f"embedding dim {d} must be divisible by code length {m}"
    code_dtype = jnp.uint8 if b <= 256 else jnp.int32   # paper: 1 byte/code
    if codes is None:  # random assignment fallback; usually pre-built
        codes = jax.random.randint(kg(), (n_items, m), 0, b,
                                   jnp.int32).astype(code_dtype)
    codes = jnp.asarray(codes).astype(code_dtype)
    assert codes.shape == (n_items, m)
    scale = init_scale if init_scale is not None else d ** -0.5
    cent = scale * jax.random.normal(kg(), (m, b, d // m))
    return {
        "codes": P(codes, ("items", "code_split")),
        "centroids": P(cent.astype(dtype), ("code_split", "centroid",
                                            "table_dim")),
    }


def lookup(p, ids):
    """ids int[...] -> embeddings [..., d]."""
    cent = p["centroids"].value               # [m, b, dk]
    m = cent.shape[0]
    codes = jnp.take(p["codes"].value, ids, axis=0).astype(jnp.int32)
    # gather per split: centroids[j, codes[..., j], :] -> [..., m, dk]
    emb = cent[jnp.arange(m), codes]
    return emb.reshape(*ids.shape, -1)


def partial_scores(p, h):
    """h [..., d] -> P [..., m, b] partial-score lookup table (fp32)."""
    cent = p["centroids"].value
    m, b, dk = cent.shape
    hs = h.reshape(*h.shape[:-1], m, dk)
    return jnp.einsum("...mk,mbk->...mb", hs.astype(jnp.float32),
                      cent.astype(jnp.float32))


def logits(p, h, *, use_kernel: bool = False):
    """h [..., d] -> scores [..., n_items] over the whole catalogue."""
    if use_kernel:
        from repro.kernels.jpq_scores import ops as kops
        return kops.jpq_scores(h, p["centroids"].value, p["codes"].value)
    part = partial_scores(p, h)                             # [..., m, b]
    codes = p["codes"].value.astype(jnp.int32)              # [N, m]
    m = codes.shape[1]

    def pick(j):                                           # [..., N]
        pj = jax.lax.dynamic_index_in_dim(part, j, part.ndim - 2, False)
        return pj[..., jax.lax.dynamic_index_in_dim(codes, j, 1, False)]

    # splits are added one at a time in a loop, in split order: unrolled,
    # XLA keeps all m gathered [..., N] buffers live for one fused sum
    # (m x the logits' bytes: 14 GB of a v5e's 16 at batch 64, L 200,
    # N 34,744), where the loop holds two
    return jax.lax.fori_loop(1, m, lambda j, s: s + pick(j),
                             pick(0))                      # [..., N] fp32


def reconstruct_table(p):
    """Materialise the full [n_items, d] table (tests / tiny catalogues)."""
    return lookup(p, jnp.arange(p["codes"].shape[0]))


def embedding_param_count(n_items: int, d: int, m: int, b: int = 256):
    """(compressed float params, full-table float params, codebook ints)."""
    return b * d, n_items * d, n_items * m

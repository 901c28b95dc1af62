"""RecJPQ embedding: codebook of sub-item centroid ids + centroid tensor.

The embedding tensor ``[n_items, d]`` is replaced by
  codes      int32 [n_items, m]   (frozen; built by repro.core.assign)
  centroids  float [m, b, d//m]   (trainable)
Item i's embedding = concat_j centroids[j, codes[i, j]]  (paper Fig. 2).

Hot paths:
  lookup(ids)      - input-side reconstruction (sequence of ids -> vectors)
  logits(h)        - score *every* item for a few hidden states h via the
                     partial-score trick: P[j,c] = <h_j, centroids[j,c]>
                     then scores_i = sum_j P[j, codes[i,j]], in split order.
                     Per query row it reads m code bytes per item instead
                     of 4d table bytes; each of the m passes reads and
                     writes the whole [..., N] scores.  Serving uses it
                     (score_last, bind_engine and the engine's materialise
                     scorer, whose PQTopK kernels match it bitwise).
  table_logits(h)  - the same scores as one product h @ E.T with the table
                     E [N, d] reconstructed once.  For the thousands of
                     rows a full-softmax training step scores, it reads
                     the table once and the MXU does the work; the
                     softmax losses use it (Embedding.loss_logits).
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


def _pick(cent, codes):
    """codes int[..., m] -> concat_j cent[j, codes[..., j]]  [..., d]."""
    m = cent.shape[0]
    # gather per split: centroids[j, codes[..., j], :] -> [..., m, dk]
    emb = cent[jnp.arange(m), codes.astype(jnp.int32)]
    return emb.reshape(*codes.shape[:-1], -1)


def lookup(p, ids):
    """ids int[...] -> embeddings [..., d]."""
    return _pick(p["centroids"].value,
                 jnp.take(p["codes"].value, ids, axis=0))


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
    """Materialise the full [n_items, d] table (``table_logits``; 71 MB
    at 34,744 items and d 512)."""
    return _pick(p["centroids"].value, p["codes"].value)


def table_logits(p, h):
    """h [..., d] -> scores [..., n_items] as ``logits`` gives them, as
    one product with the reconstructed table at the default precision
    ``partial_scores`` runs at: the same products, summed over the
    splits in another order.  Its gradient is two MXU products and the
    pick's transpose into the centroids."""
    with jax.named_scope("jpq.table_logits"):
        tab = dist.constrain(reconstruct_table(p), ("items", "table_dim"))
        return h.astype(jnp.float32) @ tab.T.astype(jnp.float32)


def embedding_param_count(n_items: int, d: int, m: int, b: int = 256):
    """(compressed float params, full-table float params, codebook ints)."""
    return b * d, n_items * d, n_items * m

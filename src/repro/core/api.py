"""Uniform embedding interface over {full, jpq, qr}.

Every backbone / assigned arch that owns an id-embedding table goes
through this factory, which is what makes RecJPQ a first-class,
config-selectable feature of the framework (``embedding.kind = "jpq"``).
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import full as _full
from repro.core import jpq as _jpq
from repro.core import qr as _qr
from repro.nn.module import KeyGen


@dataclasses.dataclass(frozen=True)
class EmbeddingConfig:
    n_items: int
    d: int
    kind: str = "full"            # full | jpq | qr
    m: int = 8                    # jpq: code length
    b: int = 256                  # jpq: centroids per split
    assignment: str = "svd"       # jpq: random | svd | bpr
    use_kernel: bool = False      # jpq: Pallas jpq_scores for logits
    init_scale: Optional[float] = None

    def float_param_count(self) -> int:
        if self.kind == "full":
            return self.n_items * self.d
        if self.kind == "jpq":
            return self.b * self.d
        if self.kind == "qr":
            q = _qr.qr_base(self.n_items)
            return ((self.n_items + q - 1) // q + q) * self.d
        raise ValueError(self.kind)


@dataclasses.dataclass(frozen=True)
class Embedding:
    cfg: EmbeddingConfig

    def init(self, kg: KeyGen, *, codes=None, dtype=jnp.float32):
        c = self.cfg
        if c.kind == "full":
            return _full.init(kg, c.n_items, c.d, dtype=dtype,
                              init_scale=c.init_scale)
        if c.kind == "jpq":
            return _jpq.init(kg, c.n_items, c.d, c.m, c.b, codes=codes,
                             dtype=dtype, init_scale=c.init_scale)
        if c.kind == "qr":
            return _qr.init(kg, c.n_items, c.d, dtype=dtype,
                            init_scale=c.init_scale)
        raise ValueError(c.kind)

    def lookup(self, p, ids):
        c = self.cfg
        if c.kind == "full":
            return _full.lookup(p, ids)
        if c.kind == "jpq":
            return _jpq.lookup(p, ids)
        return _qr.lookup(p, ids, c.n_items)

    def logits(self, p, h):
        c = self.cfg
        with jax.named_scope("item_emb.logits"):
            if c.kind == "full":
                return _full.logits(p, h)
            if c.kind == "jpq":
                return _jpq.logits(p, h, use_kernel=c.use_kernel)
            return _qr.logits(p, h, c.n_items)

    def loss_logits(self, p, h):
        """``logits`` as the full-softmax losses need them: every item
        scored for many rows at once.  JPQ scores them as one product
        with its reconstructed table (``jpq.table_logits``), which
        agrees with ``logits`` to float32 rounding, not bitwise."""
        if self.cfg.kind != "jpq":
            return self.logits(p, h)
        with jax.named_scope("item_emb.logits"):
            return _jpq.table_logits(p, h)

    def bag_lookup(self, p, ids, segment_ids, num_segments: int,
                   *, combiner: str = "sum", weights=None):
        """EmbeddingBag: ragged multi-hot pooled lookup.

        ids [nnz] int, segment_ids [nnz] int (which bag each id belongs
        to), -> [num_segments, d].  JAX has no native EmbeddingBag; this
        is gather + segment_sum per the taxonomy, with a fused Pallas
        path for the full-table kind (repro/kernels/embedding_bag).
        """
        emb = self.lookup(p, ids)                       # [nnz, d]
        if weights is not None:
            emb = emb * weights[:, None].astype(emb.dtype)
        out = jax.ops.segment_sum(emb, segment_ids, num_segments)
        if combiner == "mean":
            cnt = jax.ops.segment_sum(
                jnp.ones_like(segment_ids, emb.dtype), segment_ids,
                num_segments)
            out = out / jnp.maximum(cnt, 1.0)[:, None]
        return out


def make_embedding(cfg: EmbeddingConfig) -> Embedding:
    return Embedding(cfg)


def compression_report(cfg: EmbeddingConfig) -> dict:
    """Paper Table-2-style memory analysis for one table config."""
    base_bytes = cfg.n_items * cfg.d * 4
    if cfg.kind == "jpq":
        float_bytes = cfg.b * cfg.d * 4
        code_bytes = cfg.n_items * cfg.m * (1 if cfg.b <= 256 else 4)
        comp = float_bytes + code_bytes
    elif cfg.kind == "qr":
        comp = cfg.float_param_count() * 4
    else:
        comp = base_bytes
    return {
        "kind": cfg.kind, "n_items": cfg.n_items, "d": cfg.d,
        "base_bytes": base_bytes, "compressed_bytes": comp,
        "ratio": base_bytes / max(comp, 1),
        "pct_of_base": 100.0 * comp / base_bytes,
    }

"""Sequential recommenders: SASRec, BERT4Rec, GRU4Rec (paper backbones).

All three share the item-embedding abstraction from ``repro.core`` —
swapping ``embedding.kind`` between full / jpq / qr is the paper's whole
experiment grid.  Item ids are 1-based; row 0 is padding and row
``n_items + 1`` is BERT4Rec's [MASK] token, so every embedding table has
``n_items + 2`` rows.

Losses (paper protocol, Petrov & Macdonald replication setup):
  full_ce     - softmax over the whole catalogue (BERT4Rec, GRU).
  sampled_bce - SASRec's original one-negative-per-positive binary CE
                (needed when the catalogue makes full softmax infeasible).
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

from repro import dist
from repro.core import EmbeddingConfig, make_embedding
from repro.nn import module as nn
from repro.nn.module import P, KeyGen
from repro.nn import layers as L
from repro.nn.attention import AttnConfig, attention, attention_init
from repro.nn.recurrent import gru_init, gru_scan

NEG_INF = -1e9


@dataclasses.dataclass(frozen=True)
class SeqRecConfig:
    arch: str                     # sasrec | bert4rec | gru4rec
    n_items: int
    max_len: int = 200
    d_model: int = 512
    n_layers: int = 2
    n_heads: int = 4
    d_ff: int = 1024
    embedding: Optional[EmbeddingConfig] = None   # None -> full, d=d_model
    loss: str = "full_ce"         # full_ce | sampled_bce | code_ce
    semantic_weight: float = 0.0  # auxiliary code-CE weight (jpq only)
    n_negatives: int = 1
    dropout: float = 0.0
    mask_prob: float = 0.2        # bert4rec masking rate

    @property
    def n_rows(self) -> int:      # pad + items + [MASK]
        return self.n_items + 2

    @property
    def mask_id(self) -> int:
        return self.n_items + 1

    def emb_cfg(self) -> EmbeddingConfig:
        # SASRec/BERT4Rec init item embeddings at ~N(0, 0.02) (the same
        # scale as pos_emb).  The d**-0.5 table default, amplified by
        # the sqrt(d_model) input scaling, leaves the residual stream
        # dominated by the current item's own embedding — scores lean
        # toward input copy and early training stalls.  Only for kinds
        # where init_scale IS the embedding scale: qr composes two
        # tables multiplicatively, so 0.02 per table would square.
        base = self.embedding if self.embedding is not None else \
            EmbeddingConfig(0, 0)
        scale = base.init_scale
        if scale is None and base.kind in ("full", "jpq"):
            scale = 0.02
        return dataclasses.replace(base, n_items=self.n_rows,
                                   d=self.d_model, init_scale=scale)


def _dropout(key, x, rate):
    if rate <= 0.0 or key is None:
        return x
    keep = jax.random.bernoulli(key, 1.0 - rate, x.shape)
    return jnp.where(keep, x / (1.0 - rate), 0.0).astype(x.dtype)


class SeqRecModel:
    """SASRec / BERT4Rec / GRU4Rec with pluggable item embedding."""

    def __init__(self, cfg: SeqRecConfig, codes=None):
        self.cfg = cfg
        if (cfg.loss == "code_ce" or cfg.semantic_weight > 0.0) \
                and cfg.emb_cfg().kind != "jpq":
            raise ValueError(
                f"the semantic-ID objective (loss='code_ce' / "
                f"semantic_weight > 0) is per-position cross-entropy "
                f"over JPQ code sequences — it needs a kind='jpq' "
                f"embedding, got {cfg.emb_cfg().kind!r}")
        self.emb = make_embedding(cfg.emb_cfg())
        self._codes = codes
        self.attn_cfg = AttnConfig(
            d_model=cfg.d_model, n_heads=cfg.n_heads, n_kv=cfg.n_heads,
            head_dim=cfg.d_model // cfg.n_heads,
            causal=(cfg.arch == "sasrec"), rope=False)

    # ------------------------------------------------------------ init
    def init_params(self, rng):
        cfg = self.cfg
        kg = KeyGen(rng)
        p = {"item_emb": self.emb.init(kg, codes=self._codes)}
        if cfg.arch in ("sasrec", "bert4rec"):
            p["pos_emb"] = P(0.02 * jax.random.normal(
                kg(), (cfg.max_len, cfg.d_model)), ("seq", "embed"))
            blocks = []
            for _ in range(cfg.n_layers):
                blocks.append({
                    "ln1": L.layernorm_init(cfg.d_model),
                    "attn": attention_init(kg, self.attn_cfg),
                    "ln2": L.layernorm_init(cfg.d_model),
                    "mlp": L.dense_mlp_init(kg, cfg.d_model, cfg.d_ff),
                })
            p["blocks"] = blocks
            p["ln_f"] = L.layernorm_init(cfg.d_model)
        elif cfg.arch == "gru4rec":
            p["gru"] = [gru_init(kg, cfg.d_model, cfg.d_model)
                        for _ in range(cfg.n_layers)]
            p["proj"] = L.linear_init(kg, cfg.d_model, cfg.d_model,
                                      axes=("embed", "embed"))
        else:
            raise ValueError(cfg.arch)
        return p

    # --------------------------------------------------------- encoder
    def encode(self, p, seq, *, rng=None):
        """seq int[B, S] (0 = pad) -> hidden [B, S, d]."""
        with jax.named_scope("seqrec.encode"):
            cfg = self.cfg
            kg = KeyGen(rng) if rng is not None else None
            x = self.emb.lookup(p["item_emb"], seq)
            x = jnp.where((seq > 0)[..., None], x, 0.0)
            pad_mask = seq > 0
            if cfg.arch in ("sasrec", "bert4rec"):
                S = seq.shape[1]
                x = x * jnp.sqrt(cfg.d_model).astype(x.dtype)
                x = x + p["pos_emb"].value[:S][None]
                if kg:
                    x = _dropout(kg(), x, cfg.dropout)
                for blk in p["blocks"]:
                    h = attention(blk["attn"], self.attn_cfg,
                                  L.layernorm(blk["ln1"], x),
                                  pad_mask=pad_mask)
                    if kg:
                        h = _dropout(kg(), h, cfg.dropout)
                    x = x + h
                    h = L.dense_mlp(blk["mlp"], L.layernorm(blk["ln2"], x))
                    if kg:
                        h = _dropout(kg(), h, cfg.dropout)
                    x = x + h
                x = L.layernorm(p["ln_f"], x)
            else:                                       # gru4rec
                for gp in p["gru"]:
                    x, _ = gru_scan(gp, x)
                x = L.linear(p["proj"], x)
            return x

    # ------------------------------------------------------------ loss
    def train_loss(self, p, batch, rng=None):
        cfg = self.cfg
        if cfg.arch == "bert4rec":
            return self._masked_lm_loss(p, batch, rng)
        seq, labels = batch["seq"], batch["labels"]     # [B,S], [B,S]
        h = self.encode(p, seq, rng=rng)
        valid = labels > 0
        if cfg.loss == "full_ce":
            logits = self.emb.loss_logits(p["item_emb"], h)  # [B,S,R]
            loss = self._softmax_loss(logits, labels)
        elif cfg.loss == "code_ce":                     # semantic head
            loss = self._code_loss(p, h, labels, valid)
        else:                                           # sampled_bce
            neg = batch["negatives"]                    # [B,S,K]
            pos_e = self.emb.lookup(p["item_emb"], labels)
            neg_e = self.emb.lookup(p["item_emb"], neg)
            pos_s = jnp.sum(h * pos_e, -1)
            neg_s = jnp.einsum("bsd,bskd->bsk", h, neg_e)
            lp = jax.nn.log_sigmoid(pos_s)
            ln = jnp.sum(jax.nn.log_sigmoid(-neg_s), -1)
            loss = -jnp.sum((lp + ln) * valid) / jnp.maximum(
                jnp.sum(valid), 1)
        if cfg.semantic_weight > 0.0 and cfg.loss != "code_ce":
            aux = self._code_loss(p, h, labels, valid)
            loss = loss + cfg.semantic_weight * aux
            return loss, {"loss": loss, "code_ce": aux}
        return loss, {"loss": loss}

    def _masked_lm_loss(self, p, batch, rng):
        """BERT4Rec: batch carries pre-masked inputs + recovery targets."""
        seq, targets = batch["seq"], batch["targets"]   # targets 0 = unmasked
        h = self.encode(p, seq, rng=rng)
        valid = targets > 0
        if self.cfg.loss == "code_ce":                  # semantic head
            loss = self._code_loss(p, h, targets, valid)
            return loss, {"loss": loss}
        loss = self._softmax_loss(self.emb.loss_logits(p["item_emb"], h),
                                  targets)
        if self.cfg.semantic_weight > 0.0:
            aux = self._code_loss(p, h, targets, valid)
            loss = loss + self.cfg.semantic_weight * aux
            return loss, {"loss": loss, "code_ce": aux}
        return loss, {"loss": loss}

    def _code_loss(self, p, h, targets, valid):
        """Per-position code cross-entropy of the target items' code
        sequences (core.semantic.code_xent) — the generative head's
        training signal.  Teacher-forced per position: each code
        position's logits are the same ``partial_scores`` slices
        ``semantic_decode`` beam-searches at serve time."""
        from repro.core import semantic as _semantic
        ce = _semantic.code_xent(p["item_emb"], h, targets)   # [B, S]
        return jnp.sum(ce * valid) / jnp.maximum(jnp.sum(valid), 1)

    def _softmax_loss(self, logits, targets):
        """Full-softmax cross-entropy of ``targets`` (0 = no target),
        averaged over the positions that have one."""
        with jax.named_scope("seqrec.loss_head"):
            ce = _xent(self._mask_special(logits), targets)
            valid = targets > 0
            return jnp.sum(ce * valid) / jnp.maximum(jnp.sum(valid), 1)

    def _mask_special(self, logits):
        """Never rank pad / [MASK] rows."""
        return logits.at[..., 0].set(NEG_INF).at[..., -1].set(NEG_INF)

    # ------------------------------------------------------------ serve
    def _serve_seq(self, seq):
        """Query-position protocol: bert4rec predicts at a [MASK]
        appended after the history (the paper's next-item inference);
        causal archs query the last position of the history itself."""
        if self.cfg.arch != "bert4rec":
            return seq
        mask_col = jnp.full((seq.shape[0], 1), self.cfg.mask_id, seq.dtype)
        return jnp.concatenate([seq[:, 1:], mask_col], axis=1)

    def score_last(self, p, seq):
        """Rank the full catalogue from the last position: [B, n_rows]."""
        h = self.encode(p, self._serve_seq(seq))
        return self._mask_special(self.emb.logits(p["item_emb"], h[:, -1]))

    def bind_engine(self, p, spec, *, catalogue=None):
        """Bind a ``core.engine.RetrievalSpec`` to this model + params:
        returns a ``BoundRetrieval`` mapping a request (a [B, S]
        sequence, or a dict with ``user_hist``) through the encoder,
        the engine's scorer, and the serve protocol's post-processing.
        The engine runs at an INTERNAL k of ``min(spec.k + 2, n_rows)``
        — two extra candidates cover the pad + [MASK] rows that the
        materialised path masks before its top-k — and the post step
        demotes those rows and re-ranks, so results stay bit-equal to
        ``lax.top_k(score_last(p, seq), k)``."""
        from repro.core import engine as _engine
        n_rows = self.cfg.n_rows
        k_out = min(int(spec.k), n_rows)
        inner = dataclasses.replace(spec, k=min(k_out + 2, n_rows))
        eng = _engine.RetrievalEngine(inner, self.emb, p["item_emb"],
                                      catalogue=catalogue)

        def encode(request):
            seq = request["user_hist"] if isinstance(request, dict) \
                else request
            return self.encode(p, self._serve_seq(seq))[:, -1]

        def post(out):
            stats = None
            if inner.stats:
                v, i, stats = out
            else:
                v, i = out
            forbidden = (i == 0) | (i == n_rows - 1)
            v = jnp.where(forbidden, NEG_INF, v)
            vv, ids = _engine.rerank_candidates(v, i, k_out)
            return (vv, ids, stats) if inner.stats else (vv, ids)

        return _engine.BoundRetrieval(eng, encode, post)

    def retrieve_topk(self, p, seq, *, k: int, fused: bool = True,
                      prune=None, perm=None, warm=None, block_n=None,
                      backend=None, return_stats: bool = False):
        """Top-k catalogue retrieval from the last position WITHOUT
        materialising the [B, n_rows] score matrix ``score_last``
        builds: JPQ heads route through the engine's fused PQTopK
        scorer (optionally score-bound pruned); full/QR heads fall back
        to materialise + hierarchical top-k.  Bit-equal to
        ``lax.top_k(score_last(p, seq), k)`` — pad and [MASK] rows are
        demoted to the same NEG_INF, and the candidate re-rank
        tie-breaks on item id like a stable top-k.  ``warm`` /
        ``return_stats`` follow serve.retrieve_topk; note the stats'
        ``theta`` is the INTERNAL (k+2)-candidate threshold — exactly
        what a ThresholdState should EMA for this entrypoint.

        Compatibility wrapper over ``bind_engine`` (docs/engine.md)."""
        from repro.core import engine as _engine
        spec = _engine.spec_for(self.emb, k=k, fused=fused,
                                block_n=block_n, backend=backend,
                                prune=prune, perm=perm,
                                warm_decay=0.0 if warm is not None
                                else None,
                                stats=return_stats)
        bound = self.bind_engine(p, spec)
        if bound.engine.spec.prune:
            bound.engine.bind_catalogue(prune=prune, perm=perm)
        return bound.retrieve(seq, floor=warm)


def _xent(logits, labels):
    lse = jax.nn.logsumexp(logits, -1)
    picked = jnp.take_along_axis(logits, labels[..., None].astype(jnp.int32),
                                 -1)[..., 0]
    return lse - picked


# --------------------------------------------------- bert4rec masking

def mask_batch(rng, seq, mask_prob: float, mask_id: int):
    """Cloze-mask a batch for BERT4Rec: returns (masked_seq, targets).

    The final real item of every row is always masked (the paper
    evaluates next-item, so the model must train on the last position)
    — which also guarantees every non-empty row has at least one
    target even on an unlucky Bernoulli draw."""
    r = jax.random.uniform(rng, seq.shape)
    is_item = seq > 0
    S = seq.shape[1]
    # last real position per row (sequences are left-padded, but don't
    # rely on it): highest index with a non-pad item
    last = S - 1 - jnp.argmax(jnp.flip(is_item, axis=1), axis=1)
    force = (jnp.arange(S)[None, :] == last[:, None]) & is_item
    do_mask = ((r < mask_prob) | force) & is_item
    masked = jnp.where(do_mask, mask_id, seq)
    targets = jnp.where(do_mask, seq, 0)
    return masked, targets

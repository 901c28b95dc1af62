"""Plain reference of SASRec with a RecJPQ item embedding, in
``jax.numpy``: no kernels, no batching tricks, nothing imported from the
program under test.

It follows the architecture the configuration states (the program's
SASRec): item ``i``'s embedding is the concatenation over splits ``j``
of ``centroids[j, codes[i, j]]``; inputs are scaled by ``sqrt(d)`` and
given learned positions; each of the pre-norm blocks is causal
multi-head attention and a GELU (tanh) feed-forward, each added to the
residual; a final layer norm; scores are the dot product of the hidden
state with every item's embedding, with the pad row 0 and the [MASK]
row ``n_items + 1`` set to -1e9.  Masks are additive -1e9 biases, so
rows with nothing to attend to behave as in the program.

Parameters are the value tree ``make_values`` makes from the seed, in
the program's layout: ``item_emb.codes/centroids``, ``pos_emb``,
``blocks[i].{ln1,attn,ln2,mlp}``, ``ln_f``.

``mode`` sets the precision of every matrix product:
  "f32"  float32 inputs at ``Precision.HIGHEST`` (the reference);
  "bf16" inputs rounded to bfloat16, float32 accumulation (what the
         TPU's default precision does to float32 dots);
  "fp8"  inputs rounded to float8 e4m3, float32 accumulation (the
         control: the next precision below the configuration's).
Everything else is float32.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

NEG = -1e9
MODES = ("f32", "bf16", "fp8")


def _round(x, mode):
    if mode == "bf16":
        return x.astype(jnp.bfloat16).astype(jnp.float32)
    if mode == "fp8":
        return x.astype(jnp.float8_e4m3fn).astype(jnp.float32)
    return x


def _dot(spec, a, b, mode):
    return jnp.einsum(spec, _round(a, mode), _round(b, mode),
                      precision=jax.lax.Precision.HIGHEST)


def _layernorm(p, x, eps=1e-6):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * p["scale"] + p["bias"]


def _gelu(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        jnp.sqrt(2.0 / jnp.pi) * (x + 0.044715 * x ** 3)))


def item_table(v):
    """[n_rows, d]: every row's embedding, concatenated split by split."""
    codes = v["item_emb"]["codes"].astype(jnp.int32)
    cent = v["item_emb"]["centroids"]
    return jnp.concatenate([cent[j][codes[:, j]]
                            for j in range(cent.shape[0])], axis=-1)


def encode(v, seq, n_heads: int, mode: str = "f32"):
    """seq int [B, S] (0 = pad) -> hidden states [B, S, d]."""
    table = item_table(v)
    d = table.shape[1]
    S = seq.shape[1]
    valid = seq > 0
    x = jnp.where(valid[..., None], table[seq], 0.0)
    x = x * jnp.sqrt(jnp.float32(d)) + v["pos_emb"][:S][None]
    pos = jnp.arange(S)
    bias = jnp.where(pos[:, None] >= pos[None, :], 0.0, NEG)[None] \
        + jnp.where(valid, 0.0, NEG)[:, None, :]            # [B, S, S]
    for blk in v["blocks"]:
        a = blk["attn"]
        h = _layernorm(blk["ln1"], x)
        q = _dot("bsd,dhk->bhsk", h, a["wq"], mode)
        k = _dot("bsd,dhk->bhsk", h, a["wk"], mode)
        val = _dot("bsd,dhk->bhsk", h, a["wv"], mode)
        s = _dot("bhqk,bhtk->bhqt", q, k, mode) / jnp.sqrt(
            jnp.float32(q.shape[-1])) + bias[:, None]
        w = jax.nn.softmax(s, axis=-1)
        o = _dot("bhqt,bhtk->bhqk", w, val, mode)
        x = x + _dot("bhsk,hkd->bsd", o, a["wo"], mode)
        mlp = blk["mlp"]
        h = _layernorm(blk["ln2"], x)
        h = _gelu(_dot("bsd,df->bsf", h, mlp["wi"]["w"], mode)
                  + mlp["wi"]["b"])
        x = x + _dot("bsf,fd->bsd", h, mlp["wo"]["w"], mode) + mlp["wo"]["b"]
    return _layernorm(v["ln_f"], x)


def scores(v, h, mode: str = "f32"):
    """h [..., d] -> [..., n_rows] with pad and [MASK] rows at -1e9."""
    s = _dot("...d,nd->...n", h, item_table(v), mode)
    return s.at[..., 0].set(NEG).at[..., -1].set(NEG)


def ce_sum(v, seq, labels, n_heads: int, mode: str = "f32"):
    """Sum over positions with a label of the full-softmax cross-entropy."""
    logits = scores(v, encode(v, seq, n_heads, mode), mode)
    lse = jax.nn.logsumexp(logits, -1)
    picked = jnp.take_along_axis(logits, labels[..., None], -1)[..., 0]
    return jnp.sum(jnp.where(labels > 0, lse - picked, 0.0))


@functools.partial(jax.jit, static_argnames=("n_heads", "mode"))
def _block_grad(v, seq, labels, n_heads, mode):
    with jax.default_matmul_precision("highest"):
        return jax.value_and_grad(lambda fl: ce_sum(
            with_float_leaves(v, fl), seq, labels, n_heads, mode))(
                float_leaves(v))


def loss_and_grad(v, seq, labels, n_heads: int, mode: str = "f32",
                  rows: int = 16):
    """Mean cross-entropy over labelled positions and its gradient,
    summed over blocks of ``rows`` sequences so that it fits.  The
    gradient is a tree of the float leaves, with ``centroids`` for the
    item embedding's."""
    total, grad = 0.0, None
    n = jnp.maximum(jnp.sum(labels > 0), 1).astype(jnp.float32)
    for r in range(0, seq.shape[0], rows):
        val, g = _block_grad(v, seq[r:r + rows], labels[r:r + rows],
                             n_heads, mode)
        total = total + val
        grad = g if grad is None else jax.tree.map(jnp.add, grad, g)
    return total / n, jax.tree.map(lambda g: g / n, grad)


def adam_step(v, grads, m, s, t: int, *, lr: float, b1: float, b2: float,
              eps: float, clip_norm):
    """One Adam step on the float leaves, clipped by the global norm:
    returns (values, m, s, the clipped gradient the moments took)."""
    leaves = jax.tree.leaves(grads)
    gn = jnp.sqrt(sum(jnp.sum(jnp.square(g)) for g in leaves))
    scale = 1.0 if clip_norm is None else jnp.minimum(
        1.0, clip_norm / jnp.maximum(gn, 1e-9))
    g = jax.tree.map(lambda x: x * scale, grads)
    m = jax.tree.map(lambda a, x: b1 * a + (1 - b1) * x, m, g)
    s = jax.tree.map(lambda a, x: b2 * a + (1 - b2) * x * x, s, g)
    bc1, bc2 = 1 - b1 ** t, 1 - b2 ** t
    fl = float_leaves(v)
    new = jax.tree.map(lambda p, a, b: p - lr * ((a / bc1)
                                                 / (jnp.sqrt(b / bc2) + eps)),
                       fl, m, s)
    return with_float_leaves(v, new), m, s, g


def float_leaves(v):
    """The trainable leaves of a value tree, keyed as the gradients are."""
    out = {k: x for k, x in v.items() if k != "item_emb"}
    out["centroids"] = v["item_emb"]["centroids"]
    return out


def with_float_leaves(v, fl):
    out = {k: x for k, x in fl.items() if k != "centroids"}
    out["item_emb"] = {"codes": v["item_emb"]["codes"],
                       "centroids": fl["centroids"]}
    return out


@functools.partial(jax.jit,
                   static_argnames=("L", "d", "H", "dff", "nl", "m", "b"))
def _sasrec_values(key, codes, *, L, d, H, dff, nl, m, b):
    keys = iter(jax.random.split(key, 8 + 8 * nl))

    def normal(shape, std):
        return std * jax.random.normal(next(keys), shape, jnp.float32)

    def ln():
        return {"scale": jnp.ones((d,), jnp.float32),
                "bias": jnp.zeros((d,), jnp.float32)}

    dh = d // H
    blocks = [{
        "ln1": ln(),
        "attn": {"wq": normal((d, H, dh), d ** -0.5),
                 "wk": normal((d, H, dh), d ** -0.5),
                 "wv": normal((d, H, dh), d ** -0.5),
                 "wo": normal((H, dh, d), d ** -0.5)},
        "ln2": ln(),
        "mlp": {"wi": {"w": normal((d, dff), d ** -0.5),
                       "b": jnp.zeros((dff,), jnp.float32)},
                "wo": {"w": normal((dff, d), dff ** -0.5),
                       "b": jnp.zeros((d,), jnp.float32)}},
    } for _ in range(nl)]
    return {"item_emb": {"codes": codes,
                         "centroids": normal((m, b, d // m), 0.02)},
            "pos_emb": normal((L, d), 0.02),
            "blocks": blocks,
            "ln_f": ln()}


def make_values(config: dict, codes: np.ndarray, seed: int):
    """The value tree of SASRec + RecJPQ, on the device, in one jitted
    call from the seed: N(0, 0.02) centroids and positions, LeCun-normal
    projections, zero biases, unit layer-norm scales."""
    return _sasrec_values(
        jax.random.PRNGKey(seed % 2 ** 32), jnp.asarray(codes),
        L=config["max_len"], d=config["d_model"], H=config["n_heads"],
        dff=config["d_ff"], nl=config["n_layers"], m=config["m"],
        b=config["b"])

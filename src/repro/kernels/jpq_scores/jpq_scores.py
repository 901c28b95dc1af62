"""Pallas TPU kernel: RecJPQ full-catalogue scoring through codes.

Problem: given partial-score LUTs ``P [B, m, b]`` (already computed as
``P[t,j,c] = <h_t[j·dk:(j+1)·dk], centroids[j,c]>`` — a tiny MXU matmul
done outside the kernel) and the codebook ``codes [N, m]``, produce
``scores [B, N] = sum_j P[:, j, codes[i, j]]``.

TPU adaptation (vs. the GPU scatter/gather formulation): a per-item
gather from the LUT would serialise on the VPU; instead each ``[Nt]``
item tile builds a one-hot matrix ``O_j [b, Nt]`` from its codes and the
gather-sum becomes ``m`` MXU matmuls ``P[:, j, :] @ O_j`` accumulated in
fp32.  The LUT tile (``Bt·m·b`` fp32) and the codes tile (``Nt·m`` int32)
both live in VMEM; HBM traffic per item is ``m`` codes (``4·m`` bytes
as int32, ``m`` bytes at the paper's uint8 design point) instead of
``4·d`` table bytes — the compression claim of the paper, realised as a
bandwidth win at serving time.

Layout: the kernel reads the LUT as ``[m, B, b]`` and the codes as
``[m, N]`` int32 (the wrapper transposes both), so split ``j`` of a
tile is a whole ``[Bt, b]`` LUT slab and a lane-dense ``[1, Nt]`` code
row; the one-hot ``[b, Nt]`` is a sublane broadcast of that row.  A
``[Nt, m]`` codes block instead puts ``m`` on the lane axis, and the
per-split column then has to be relaid out into a row inside the
kernel.  The contraction is exact by construction (``tile_scores``):
a default-precision fp32 matmul would round ``x`` to bf16.

Grid: ``(B/Bt, N/Nt)``; both dims parallel (no cross-step accumulation).
VMEM per step (defaults Bt=256, Nt=512, m=8, b=256):
  P tile  8·256·256·4  = 2.0 MiB
  codes   8·512·4      = 16 KiB
  one-hot 256·512·2    = 0.25 MiB (bf16, transient, per j)
  out     256·512·4    = 0.5 MiB                      -> ~3 MiB << 16 MiB VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def bf16_parts(x):
    """fp32 ``x`` as three bf16 parts with ``x == (hi + mid) + lo``
    exactly in fp32: bf16 keeps 8 of fp32's 24 significant bits, and
    each remainder is exact (``x - hi`` and ``r - mid`` lose nothing)."""
    hi = x.astype(jnp.bfloat16)
    r = x - hi.astype(jnp.float32)
    mid = r.astype(jnp.bfloat16)
    return hi, mid, (r - mid.astype(jnp.float32)).astype(jnp.bfloat16)


def tile_scores(p_ref, codes_ref):
    """p_ref [m, Bt, b] fp32 LUT tile, codes_ref [m, Nt] int32 codes
    tile -> scores [Bt, Nt] fp32, ``sum_j P[j, :, codes[j, i]]`` summed
    in split order (bit-equal to the gather reference).

    Each split's pick is three single-pass bf16 one-hot matmuls, one
    per bf16 part of the LUT: every product is exact and the only
    non-zero one per output, so each result is exact, and so is their
    sum.  One fp32 matmul at ``Precision.HIGHEST`` added onto the
    running sum was not exact on a v5e: at the Gowalla size some
    scores came out up to an ulp off the gather reference."""
    m, bt, b = p_ref.shape
    nt = codes_ref.shape[1]
    centroid_ids = jax.lax.broadcasted_iota(jnp.int32, (b, nt), 0)
    acc = None
    for j in range(m):                       # static unroll over code splits
        onehot = (codes_ref[j:j + 1, :] == centroid_ids).astype(jnp.bfloat16)
        hi, mid, lo = (jnp.dot(part, onehot,
                               preferred_element_type=jnp.float32)
                       for part in bf16_parts(p_ref[j]))
        pick = (hi + mid) + lo
        acc = pick if acc is None else acc + pick
    return acc


def kernel_operands(partial, codes):
    """[B, m, b] LUT and [N, m] codes -> the kernels' ``[m, B, b]`` fp32
    and ``[m, N]`` int32 operands."""
    return (jnp.transpose(partial.astype(jnp.float32), (1, 0, 2)),
            codes.astype(jnp.int32).T)


def _kernel(p_ref, codes_ref, o_ref):
    o_ref[...] = tile_scores(p_ref, codes_ref)


@functools.partial(jax.jit, static_argnames=("block_b", "block_n",
                                             "interpret"))
def jpq_scores_lut(partial, codes, *, block_b: int = 256,
                   block_n: int = 512, interpret: bool = False):
    """partial [B, m, b] fp32, codes [N, m] int -> scores [B, N] fp32.

    B and N must be padded to block multiples by the caller (ops.py).
    """
    B, m, b = partial.shape
    N = codes.shape[0]
    assert B % block_b == 0 and N % block_n == 0, (B, N, block_b, block_n)
    grid = (B // block_b, N // block_n)
    return pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((m, block_b, b), lambda i, n: (0, i, 0)),
            pl.BlockSpec((m, block_n), lambda i, n: (0, n)),
        ],
        out_specs=pl.BlockSpec((block_b, block_n), lambda i, n: (i, n)),
        out_shape=jax.ShapeDtypeStruct((B, N), jnp.float32),
        interpret=interpret,
        name="jpq_scores",
    )(*kernel_operands(partial, codes))

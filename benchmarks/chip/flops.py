"""Operation counts of SASRec with RecJPQ, by hand from the shapes.
Shared by the per-layer metrics that divide them by a time."""
from __future__ import annotations


def encoder_position(c: dict, S: int) -> float:
    """Forward FLOPs of the encoder at one position of a length-S
    sequence: per block, the q/k/v/o projections (8 d^2), scores and
    weighted sum over all S positions (4 S d) and the feed-forward
    (4 d d_ff)."""
    d = c["d_model"]
    return c["n_layers"] * (8 * d * d + 4 * S * d + 4 * d * c["d_ff"])


def lut(c: dict) -> float:
    """Forward FLOPs of one hidden state's partial-score table: m splits
    of b centroids of d/m, a multiply and an add each."""
    return 2 * c["b"] * c["d_model"]


def train_sequence(c: dict) -> float:
    """Model FLOPs of one training sequence of ``max_len`` positions:
    the encoder and the partial-score table forward and backward (3x),
    the gather-sum of m splits over every row forward and its
    scatter-add backward (2 m N), and the softmax cross-entropy over
    every row forward and backward (4 N)."""
    L = c["max_len"]
    rows = c["n_items"] + 2
    matmul = encoder_position(c, L) + lut(c)
    return L * (3 * matmul + 2 * c["m"] * rows + 4 * rows)

"""The code table and corpus of a configuration, made by the benchmark
from the seed, and the program's model built around the weights that
the configuration's reference module makes (``make_values``).

The program receives these values; the reference reads the same values.
Neither side's weights come from the other.
"""
from __future__ import annotations

import jax
import numpy as np

from chip.traffic import codes as codes_mod
from chip.traffic import sessions


def corpus_and_codes(config: dict, seed: int):
    """(catalogue, items, lengths, codes [n_rows, m]) of the seeded
    corpus.  Rows are 1-based items plus the pad row 0 and the [MASK]
    row ``n_items + 1``, which get codes of their own like any row."""
    n_items = config["n_items"]
    cat, items, lengths = sessions.corpus(n_items, config["data"], seed)
    users, rows = sessions.interactions(items, lengths)
    codes = codes_mod.svd_codes(users, rows + 1, items.shape[0],
                                n_items + 2, config["m"], config["b"],
                                seed=seed)
    return cat, items, lengths, codes


def popularity_counts(items: np.ndarray, lengths: np.ndarray,
                      n_rows: int) -> np.ndarray:
    """Per-row interaction counts of the held-in part of the corpus."""
    _, rows = sessions.interactions(items, lengths)
    return np.bincount(rows + 1, minlength=n_rows)


def program_model(config: dict, values):
    """The program's ``SeqRecModel`` for ``config`` and its parameter
    tree holding ``values``.  Raises if the program's parameter layout
    differs from the benchmark's."""
    from repro.core import EmbeddingConfig
    from repro.models.sequential import SeqRecConfig, SeqRecModel
    from repro.nn import module as nn
    cfg = SeqRecConfig(
        arch=config["arch"], n_items=config["n_items"],
        max_len=config["max_len"], d_model=config["d_model"],
        n_layers=config["n_layers"], n_heads=config["n_heads"],
        d_ff=config["d_ff"], loss=config["loss"],
        embedding=EmbeddingConfig(0, 0, kind="jpq", m=config["m"],
                                  b=config["b"]))
    model = SeqRecModel(cfg)
    meta = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))
    want = jax.tree.map(lambda x: (x.shape, str(x.dtype)),
                        nn.values(meta))
    have = jax.tree.map(lambda x: (x.shape, str(x.dtype)), values)
    if want != have:
        raise RuntimeError(
            f"the program's parameter layout is not the benchmark's:\n"
            f"program {want}\nbenchmark {have}")
    return model, nn.with_values(meta, values)

"""A serving mix run end to end on the CPU at a tiny size, skipping only
the harness's look for a chip.  The reference scores from the last item
of each history, left-padded as the program trains; the server pads
histories on the right and scores from the last position, a pad slot
for every history shorter than its bucket (PERF.md, Open questions), so
the sound run here has the server's batches left-padded.  So served, it
is correct under the tightest serving limit read on the chip; with an
answer altered where the pool hands it back, or with the control (the
reference at float8 inputs) choosing the items, it is not, under the
loosest.  And the command refuses to run without an accelerator."""
from __future__ import annotations

import os
import subprocess
import sys
import time

import numpy as np

import jax
import jax.numpy as jnp

from chip import calibrate, harness, run, weights
from chip.drivers import serve
from chip.tests import small

# the serving cells' limits as last read on the chip (PERF.md §6): the
# tighter for the sound run, the looser for the broken ones
TIGHT = {"score_gap": 0.033}
LOOSE = {"score_gap": 0.042}


def _run(limits, fault=None, seed=4):
    return run.run_cell("tiny-serve", seed, 0.5, False, require_chip=False,
                        fault=fault,
                        cell_files=small.cell_files("serve", limits))


def test_sound_run_is_correct():
    with calibrate.left_padded_batches():
        result, checks = _run(TIGHT, seed=2 ** 31 + 5)
    assert result["correct"], checks
    assert result["attempted"] > 0 and result["failed"] == 0
    assert set(result["metrics"]) == {"serve_p50_ms", "serve_done_per_s",
                                      "setup_s"}


def test_altered_answer_is_not_correct():
    with calibrate.left_padded_batches():
        result, checks = _run(LOOSE, fault="answer")
    assert not result["correct"], checks


def test_control_fails_the_limits():
    c = serve.Cell(config=small.CONFIG, traffic=small.SERVE, seed=6,
                   spans=harness.Spans())
    c.setup()
    c.prepare(0.5)
    c.window(0.5, time.perf_counter)
    c.free()
    idx = c.sample()
    want = c.reference_scores(idx, "f32")
    gap = c.gap(c.control_served(idx, "fp8"), want)
    assert gap > LOOSE["score_gap"], gap


def test_reference_scores_from_the_last_item_as_the_program_trains():
    # the history sits at the end of its bucket, after the pad
    assert serve.padded(np.array([5, 6, 7]), 5).tolist() == [0, 0, 5, 6, 7]
    assert serve.padded(np.arange(1, 9), 5).tolist() == [4, 5, 6, 7, 8]
    # and the reference's scores from it are the program's own
    # ``score_last`` on the same left-padded rows
    cfg = small.CONFIG
    _, items, lengths, codes = weights.corpus_and_codes(cfg, 7)
    values = harness.reference(cfg).make_values(cfg, codes, 7)
    model, params = weights.program_model(cfg, values)
    seq = np.stack([serve.padded(items[i, :lengths[i]], 16)
                    for i in range(4)])
    got = np.asarray(model.score_last(params, jnp.asarray(seq)))
    want = np.asarray(serve._ref_scores(
        harness.reference(cfg), jax.tree.map(jnp.asarray, values),
        jnp.asarray(seq), cfg["n_heads"], "f32"))
    real = slice(1, cfg["n_items"] + 1)
    np.testing.assert_allclose(got[:, real], want[:, real], rtol=0,
                               atol=1e-4)


def test_command_refuses_to_run_without_an_accelerator():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(harness.HERE, "run.py"),
         "--workload", "booking-train", "--seed", "1", "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True, env=env,
        timeout=120, cwd=harness.ROOT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no accelerator" in p.stderr

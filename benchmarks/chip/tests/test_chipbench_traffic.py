"""The benchmark's generators: exact repeats by seed, the popularity skew
the pruned sweep responds to, and agreement with the loops they
vectorise."""
from __future__ import annotations

import numpy as np
import pytest

from chip.traffic import arrivals, codes, sessions

PARAMS = {"n_users": 500, "n_clusters": 5, "zipf_a": 1.2,
          "stay_prob": 0.85, "min_len": 3, "max_len": 30}


def test_corpus_repeats_exactly_by_seed():
    a = sessions.corpus(2000, PARAMS, 7)
    b = sessions.corpus(2000, PARAMS, 7)
    c = sessions.corpus(2000, PARAMS, 8)
    assert np.array_equal(a[1], b[1]) and np.array_equal(a[2], b[2])
    assert not np.array_equal(a[1], c[1])


def test_sessions_have_their_lengths_and_valid_items():
    cat, items, lengths = sessions.corpus(2000, PARAMS, 3)
    assert lengths.min() >= 3 and lengths.max() <= 30
    filled = np.arange(items.shape[1])[None, :] < lengths[:, None]
    assert np.all(items[filled] >= 1) and np.all(items[filled] <= 2000)
    assert np.all(items[~filled] == 0)


def test_items_stay_in_their_cluster_between_jumps():
    rng = np.random.default_rng(0)
    cat = sessions.Catalogue(1000, n_clusters=4, zipf_a=1.2, rng=rng)
    clusters = np.full((2000,), 2)
    drawn = cat.draw(clusters, rng) - 1
    members = set(cat.items_by_cluster[cat.start[2]:cat.start[3]].tolist())
    assert set(drawn.tolist()) <= members


def test_popularity_is_skewed():
    """Zipf 1.2: the most popular hundredth of the catalogue takes far
    more than a hundredth of the draws."""
    _, items, lengths = sessions.corpus(
        5000, dict(PARAMS, n_users=3000), 11)
    counts = np.bincount(items[items > 0], minlength=5001)[1:]
    top = np.sort(counts)[::-1][:50].sum()
    assert top / counts.sum() > 0.3


def test_draws_follow_popularity_within_a_cluster():
    rng = np.random.default_rng(1)
    cat = sessions.Catalogue(50, n_clusters=1, zipf_a=1.2, rng=rng)
    drawn = cat.draw(np.zeros(200_000, np.int64), rng) - 1
    freq = np.bincount(drawn, minlength=50) / drawn.size
    want = cat.pop / cat.pop.sum()
    assert np.max(np.abs(freq - want)) < 0.01


def test_left_pad_matches_the_loop_it_replaces():
    _, items, lengths = sessions.corpus(300, PARAMS, 5)
    for L in (4, 10, 40):
        got = sessions.left_pad(items, lengths, L)
        for r in range(items.shape[0]):
            s = items[r, :lengths[r]][-L:]
            want = np.zeros(L, items.dtype)
            want[L - s.size:] = s
            assert np.array_equal(got[r], want)


def test_interactions_hold_out_the_last_two():
    _, items, lengths = sessions.corpus(300, PARAMS, 5)
    users, rows = sessions.interactions(items, lengths)
    assert users.size == int(np.sum(lengths - 2))
    r = 0
    assert np.array_equal(rows[users == r] + 1, items[r, :lengths[r] - 2])


def test_svd_codes_repeat_by_seed_and_fill_the_bins():
    _, items, lengths = sessions.corpus(3000, dict(PARAMS, n_users=800), 2)
    users, rows = sessions.interactions(items, lengths)
    a = codes.svd_codes(users, rows, 800, 3000, 4, 16, seed=9)
    b = codes.svd_codes(users, rows, 800, 3000, 4, 16, seed=9)
    assert a.dtype == np.uint8 and a.shape == (3000, 4)
    assert np.array_equal(a, b)
    assert a.max() < 16
    # equal-mass quantile bins: every code of every split is used
    for j in range(4):
        assert np.bincount(a[:, j], minlength=16).min() > 0


@pytest.mark.parametrize("rate", [50.0, 2000.0])
def test_arrivals_repeat_by_seed_and_keep_their_rate(rate):
    a = arrivals.poisson_arrivals(rate, 20.0, np.random.default_rng(4))
    b = arrivals.poisson_arrivals(rate, 20.0, np.random.default_rng(4))
    assert np.array_equal(a, b)
    assert np.all(np.diff(a) > 0) and a.max() < 20.0
    assert abs(a.size / 20.0 - rate) < 5 * np.sqrt(rate / 20.0)


def test_every_seed_serves_the_same_gaps_and_lengths():
    a_due, a_len = arrivals.schedule(500.0, 10.0, 3, 40, seed=1)
    b_due, b_len = arrivals.schedule(500.0, 10.0, 3, 40, seed=2 ** 31 + 9)
    assert a_due.size == b_due.size and a_due.max() < 10.0
    assert np.allclose(np.sort(np.diff(a_due, prepend=0.0)),
                       np.sort(np.diff(b_due, prepend=0.0)))
    assert np.array_equal(np.sort(a_len), np.sort(b_len))
    assert not np.array_equal(a_len, b_len)
    assert a_len.min() >= 3 and a_len.max() <= 40
    c_due, c_len = arrivals.schedule(500.0, 10.0, 3, 40, seed=1)
    assert np.array_equal(a_due, c_due) and np.array_equal(a_len, c_len)

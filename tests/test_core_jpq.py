"""Property + unit tests for the paper's core: RecJPQ embeddings,
assignment strategies, and the QR baseline."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import EmbeddingConfig, build_codebook, make_embedding
from repro.core import jpq, qr
from repro.core.api import compression_report
from repro.nn.module import KeyGen

settings.register_profile("ci", max_examples=20, deadline=None)
settings.load_profile("ci")


@st.composite
def jpq_dims(draw):
    m = draw(st.sampled_from([1, 2, 4, 8]))
    dk = draw(st.sampled_from([1, 2, 8]))
    b = draw(st.sampled_from([2, 16, 256]))
    n = draw(st.integers(min_value=1, max_value=300))
    return n, m * dk, m, b


class TestJPQ:
    @given(jpq_dims())
    def test_reconstruction_is_centroid_concat(self, dims):
        """Paper Fig. 2: e_i = concat_j centroids[j, codes[i, j]]."""
        n, d, m, b = dims
        p = jpq.init(KeyGen(0), n, d, m, b)
        cent = np.asarray(p["centroids"].value)
        codes = np.asarray(p["codes"].value)
        tab = np.asarray(jpq.reconstruct_table(p))
        i = n // 2
        expected = np.concatenate([cent[j, codes[i, j]] for j in range(m)])
        np.testing.assert_allclose(tab[i], expected, rtol=1e-6)

    @given(jpq_dims())
    def test_logits_equal_full_table_scores(self, dims):
        """The partial-score trick must equal h @ table.T exactly
        (same floating-point contraction, fp32)."""
        n, d, m, b = dims
        p = jpq.init(KeyGen(1), n, d, m, b)
        h = jax.random.normal(jax.random.PRNGKey(2), (5, d))
        tab = jpq.reconstruct_table(p)
        np.testing.assert_allclose(
            np.asarray(jpq.logits(p, h)),
            np.asarray(h @ tab.T), rtol=1e-4, atol=1e-4)

    def test_codes_are_one_byte(self):
        p = jpq.init(KeyGen(0), 100, 32, 8, 256)
        assert p["codes"].value.dtype == jnp.uint8   # paper: k=1 byte

    def test_param_count_independent_of_catalogue(self):
        c1 = EmbeddingConfig(n_items=1000, d=64, kind="jpq", m=8)
        c2 = EmbeddingConfig(n_items=1_000_000, d=64, kind="jpq", m=8)
        assert c1.float_param_count() == c2.float_param_count() == 256 * 64

    def test_grad_flows_to_centroids_not_codes(self):
        p = jpq.init(KeyGen(0), 50, 16, 4, 8)
        from repro.nn import module as nn
        vals = nn.values(p)

        def loss(v):
            pp = nn.with_values(p, v)
            return jnp.sum(jpq.logits(pp, jnp.ones((2, 16))) ** 2)
        g = jax.grad(loss, allow_int=True)(vals)
        assert float(jnp.abs(g["centroids"]).sum()) > 0
        # int codes produce float0 tangents (no update possible)
        assert g["codes"].dtype == jax.dtypes.float0


def _table_case(n, d, m, b, repeated):
    """A JPQ table and hidden states; ``repeated`` draws every code from
    two centroid ids, so many items share each of them."""
    codes = None
    if repeated:
        codes = np.random.default_rng(0).integers(0, 2, (n, m))
    p = jpq.init(KeyGen(3), n, d, m, b, codes=codes)
    h = jax.random.normal(jax.random.PRNGKey(4), (2, 3, d))
    return p, h


TABLE_CASES = [
    pytest.param(300, 32, 4, 16, False, id="n300"),      # N % 128 != 0
    pytest.param(257, 16, 8, 4, True, id="n257-repeated-ids"),
    pytest.param(512, 64, 8, 256, False, id="n512-b256"),
]


class TestTableRoute:
    """``table_logits`` scores the catalogue as one product with the
    reconstructed table: the partial-score route's numbers to float32
    rounding, and its gradients."""

    @pytest.mark.parametrize("n, d, m, b, repeated", TABLE_CASES)
    def test_scores_match_partial_route_and_table(self, n, d, m, b,
                                                   repeated):
        p, h = _table_case(n, d, m, b, repeated)
        got = jpq.table_logits(p, h)
        assert got.shape == (2, 3, n) and got.dtype == jnp.float32
        np.testing.assert_allclose(np.asarray(got),
                                   np.asarray(jpq.logits(p, h)),
                                   rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(h @ jpq.reconstruct_table(p).T),
            rtol=1e-5, atol=1e-5)

    @pytest.mark.parametrize("n, d, m, b, repeated", TABLE_CASES)
    def test_gradients_match_partial_route(self, n, d, m, b, repeated):
        from repro.nn import module as nn
        p, h = _table_case(n, d, m, b, repeated)
        w = jax.random.normal(jax.random.PRNGKey(5), (2, 3, n))

        def grads(route):
            def loss(v, hh):
                s = route(nn.with_values(p, v), hh)
                return jnp.sum(w * s) + jnp.sum(jax.nn.logsumexp(s, -1))
            return jax.grad(loss, argnums=(0, 1), allow_int=True)(
                nn.values(p), h)

        (gv, gh), (rv, rh) = grads(jpq.table_logits), grads(jpq.logits)
        np.testing.assert_allclose(np.asarray(gh), np.asarray(rh),
                                   rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(np.asarray(gv["centroids"]),
                                   np.asarray(rv["centroids"]),
                                   rtol=1e-5, atol=1e-5)
        assert gv["codes"].dtype == jax.dtypes.float0

    @pytest.mark.parametrize("arch", ["sasrec", "bert4rec"])
    def test_full_ce_loss_takes_the_table_route(self, arch, monkeypatch):
        """The full-softmax loss runs ``loss_logits``: the compiled step
        names ``jpq.table_logits`` inside ``item_emb.logits`` both ways,
        and the loss is the partial-score route's."""
        import re

        from repro.core.api import Embedding
        from repro.models.sequential import SeqRecConfig, SeqRecModel
        from repro.nn import module as nn
        from repro.train.loop import TrainConfig, Trainer
        from repro.train.optimizer import OptConfig, init_opt_state
        cfg = SeqRecConfig(arch=arch, n_items=140, max_len=8, d_model=16,
                           n_layers=1, n_heads=2, d_ff=32,
                           embedding=EmbeddingConfig(0, 0, kind="jpq",
                                                     m=4, b=8))
        model = SeqRecModel(cfg)
        meta = model.init_params(jax.random.PRNGKey(0))
        values = nn.values(meta)
        seq = jax.random.randint(jax.random.PRNGKey(1), (4, 8), 0, 141)
        batch = {"seq": seq, "labels": seq, "targets": seq}
        text = jax.jit(Trainer(model, OptConfig(), TrainConfig(batch_size=4),
                               data_fn=None)._build_step(meta)).lower(
            values, init_opt_state(values), batch,
            jax.random.PRNGKey(2)).compile().as_text()
        names = [n for n in re.findall(r'op_name="([^"]*)"', text)
                 if "item_emb.logits" in n and "jpq.table_logits" in n]
        assert any("transpose(" not in n for n in names)
        assert any("transpose(jvp(item_emb.logits))" in n for n in names)

        def loss():
            return float(model.train_loss(meta, batch)[0])
        got = loss()
        monkeypatch.setattr(Embedding, "loss_logits", Embedding.logits)
        assert got == pytest.approx(loss(), rel=1e-5)


class TestAssignments:
    def _interactions(self, n_users=60, n_items=120, n=3000, seed=0):
        rng = np.random.default_rng(seed)
        # two disjoint user populations -> strong item clusters
        u = rng.integers(0, n_users, n)
        half = n_items // 2
        i = np.where(u < n_users // 2,
                     rng.integers(0, half, n),
                     rng.integers(half, n_items, n))
        return u, i, n_users, n_items

    @pytest.mark.parametrize("strategy", ["random", "svd", "bpr"])
    def test_codes_shape_and_range(self, strategy):
        u, i, nu, ni = self._interactions()
        codes = build_codebook(strategy, ni, 4, 16, interactions=(u, i),
                               n_users=nu, seed=0,
                               **({"epochs": 2} if strategy == "bpr" else {}))
        assert codes.shape == (ni, 4)
        assert codes.min() >= 0 and codes.max() < 16

    def test_svd_quantiles_are_balanced(self):
        """Equal-mass binning: each centroid id gets ~n_items/b items."""
        u, i, nu, ni = self._interactions()
        codes = build_codebook("svd", ni, 4, 8, interactions=(u, i),
                               n_users=nu, seed=0)
        for j in range(4):
            counts = np.bincount(codes[:, j], minlength=8)
            assert counts.max() <= 3 * ni / 8, counts

    def test_svd_groups_similar_items(self):
        """Items co-consumed by the same users should share more code
        components than items from the other cluster (Limitation L4)."""
        u, i, nu, ni = self._interactions()
        codes = build_codebook("svd", ni, 8, 8, interactions=(u, i),
                               n_users=nu, seed=0)
        half = ni // 2
        rng = np.random.default_rng(1)

        def mean_shared(a_pool, b_pool):
            tot = 0
            for _ in range(300):
                a = rng.choice(a_pool)
                b = rng.choice(b_pool)
                tot += np.sum(codes[a] == codes[b])
            return tot / 300

        within = 0.5 * (mean_shared(np.arange(half), np.arange(half))
                        + mean_shared(np.arange(half, ni),
                                      np.arange(half, ni)))
        across = mean_shared(np.arange(half), np.arange(half, ni))
        assert within > across + 0.3, (within, across)

    def test_deterministic(self):
        u, i, nu, ni = self._interactions()
        c1 = build_codebook("svd", ni, 4, 8, interactions=(u, i),
                            n_users=nu, seed=7)
        c2 = build_codebook("svd", ni, 4, 8, interactions=(u, i),
                            n_users=nu, seed=7)
        np.testing.assert_array_equal(c1, c2)

    def test_seed_streams_are_spawned_children(self):
        """RNG-discipline pin (deliberate bitstream change): every
        stage's stream is a ``SeedSequence(seed).spawn`` child, not the
        raw integer — seeding embeddings and discretise noise with the
        SAME integer made the noise replay the embedding bitstream."""
        for seed in (0, 7):
            embed_ss, disc_ss = np.random.SeedSequence(seed).spawn(2)
            got = build_codebook("random", 50, 3, 16, seed=seed)
            want = np.random.default_rng(embed_ss).integers(
                0, 16, (50, 3), dtype=np.int32)
            np.testing.assert_array_equal(got, want)
            # the two children never collapse to one stream
            a = np.random.default_rng(embed_ss).integers(0, 2**30, 8)
            b = np.random.default_rng(disc_ss).integers(0, 2**30, 8)
            assert not np.array_equal(a, b)

    def test_discretise_stream_independent_of_embedding_stream(self):
        """svd's code draw must not change if ONLY the discretise
        child's consumption pattern would have (the old same-integer
        seeding coupled them); equivalently, the svd pipeline equals
        explicitly re-running its two stages on the spawned children."""
        from repro.core.assign import _discretise, svd_item_embeddings
        u, i, nu, ni = self._interactions()
        embed_ss, disc_ss = np.random.SeedSequence(3).spawn(2)
        emb = svd_item_embeddings(u, i, nu, ni, 4, seed=embed_ss)
        want = _discretise(emb, 8, np.random.default_rng(disc_ss))
        got = build_codebook("svd", ni, 4, 8, interactions=(u, i),
                            n_users=nu, seed=3)
        np.testing.assert_array_equal(got, want)


class TestPopularityPermutationValidation:
    def test_valid_counts_pass(self):
        from repro.core.assign import popularity_permutation
        perm = popularity_permutation(np.array([1.0, 5.0, 5.0, 0.0]))
        np.testing.assert_array_equal(perm, [1, 2, 0, 3])  # stable ties

    def test_rejects_nan(self):
        from repro.core.assign import popularity_permutation
        with pytest.raises(ValueError, match="NaN"):
            popularity_permutation(np.array([1.0, np.nan, 2.0]))

    def test_rejects_negative(self):
        from repro.core.assign import popularity_permutation
        with pytest.raises(ValueError, match="negative"):
            popularity_permutation(np.array([3, -1, 2]))

    def test_rejects_length_mismatch_and_ndim(self):
        from repro.core.assign import popularity_permutation
        with pytest.raises(ValueError, match="n_items"):
            popularity_permutation(np.arange(5), n_items=6)
        with pytest.raises(ValueError, match="1-D"):
            popularity_permutation(np.ones((4, 2)))


class TestQR:
    @given(st.integers(min_value=2, max_value=500))
    def test_unique_codes(self, n_items):
        """QR guarantees a unique (quotient, remainder) pair per item."""
        q = qr.qr_base(n_items)
        ids = np.arange(n_items)
        pairs = set(zip(ids // q, ids % q))
        assert len(pairs) == n_items

    def test_logits_match_lookup_scores(self):
        p = qr.init(KeyGen(0), 77, 16)
        h = jax.random.normal(jax.random.PRNGKey(1), (3, 16))
        tab = qr.lookup(p, jnp.arange(77), 77)
        np.testing.assert_allclose(
            np.asarray(qr.logits(p, h, 77)), np.asarray(h @ tab.T),
            rtol=1e-4, atol=1e-4)


class TestCompressionReport:
    def test_paper_table2_gowalla_row(self):
        """Table 2: Gowalla (1,280,969 items, d=512, m=8, b=2048->but the
        paper's fixed b=256/k=1 row is 0.160% at code length 8)."""
        rep = compression_report(EmbeddingConfig(
            n_items=1_280_969, d=512, kind="jpq", m=8, b=256))
        # codes dominate: 8 bytes/item vs 2048 bytes/item full
        assert rep["pct_of_base"] < 1.0
        assert rep["ratio"] > 100

    def test_full_is_identity(self):
        rep = compression_report(EmbeddingConfig(1000, 64, kind="full"))
        assert rep["ratio"] == 1.0

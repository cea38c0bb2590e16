"""K-Means quantization unit + property tests (core/quantization.py)."""
import jax
import jax.numpy as jnp
import numpy as np

from tests._hypothesis_compat import given, settings, st

from repro.core import quantization as quant


def test_kmeans_reduces_mse(rng):
    x = jax.random.normal(rng, (512, 16))
    cfg = quant.KMeansConfig(k=32, iters=15)
    cents, mses = quant.kmeans_fit(rng, x, cfg)
    assert cents.shape == (32, 16)
    assert float(mses[-1]) <= float(mses[0])
    # codebook should beat a random codebook
    rand_cents = jax.random.normal(jax.random.PRNGKey(9), (32, 16))
    assert (quant.quantization_error(x, cents)
            < quant.quantization_error(x, rand_cents))


def test_kmeans_recovers_planted_clusters(rng):
    centers = jax.random.normal(rng, (8, 8)) * 5
    idx = jax.random.randint(jax.random.PRNGKey(1), (1024,), 0, 8)
    x = centers[idx] + 0.05 * jax.random.normal(jax.random.PRNGKey(2),
                                                (1024, 8))
    cents, _ = quant.kmeans_fit(rng, x, quant.KMeansConfig(k=8, iters=25))
    err = quant.quantization_error(x, cents)
    assert float(err) < 0.1  # ~noise floor (8 dims * 0.05^2 = 0.02)


def test_assign_is_nearest(rng):
    x = jax.random.normal(rng, (64, 4))
    c = jax.random.normal(jax.random.PRNGKey(3), (7, 4))
    codes = quant.assign(x, c)
    d = jnp.sum((x[:, None] - c[None]) ** 2, -1)
    np.testing.assert_array_equal(np.asarray(codes), np.argmin(d, -1))


def test_quantize_decode_shapes_and_dtype(rng):
    x = jax.random.normal(rng, (10, 6, 16))
    c = jax.random.normal(jax.random.PRNGKey(3), (256, 16))
    codes = quant.quantize(x, c)
    assert codes.shape == (10, 6) and codes.dtype == jnp.uint8
    dec = quant.decode(codes, c)
    assert dec.shape == x.shape


@settings(max_examples=20, deadline=None)
@given(k=st.sampled_from([128, 256, 512]))
def test_paper_bits_arithmetic(k):
    """b = ceil(log2 K): 7/8/9 bits for the paper's K values."""
    cfg = quant.KMeansConfig(k=k)
    assert cfg.bits == {128: 7, 256: 8, 512: 9}[k]
    assert cfg.code_dtype == (jnp.uint8 if k <= 256 else jnp.uint16)


@settings(max_examples=10, deadline=None)
@given(n=st.integers(32, 128), d=st.sampled_from([4, 8]),
       k=st.sampled_from([4, 16]))
def test_property_decode_error_bounded_by_worst_pair(n, d, k):
    """Reconstruction error <= max pairwise distance (codebook covers x)."""
    key = jax.random.PRNGKey(n * d * k)
    x = jax.random.normal(key, (n, d))
    cents, _ = quant.kmeans_fit(key, x, quant.KMeansConfig(k=k, iters=5))
    codes = quant.assign(x, cents)
    err = jnp.sum((x - quant.decode(codes, cents)) ** 2, -1)
    # nearest-centroid property: err <= distance to ANY centroid
    d_all = jnp.sum((x[:, None] - cents[None]) ** 2, -1)
    assert bool(jnp.all(err <= jnp.min(d_all, -1) + 1e-5))


def test_pairwise_sq_dists_clamped_non_negative():
    """Satellite: float cancellation yields negative squared distances on
    large-norm inputs; the clamp must keep every consumer (k-means++
    weights, inertia) on valid values."""
    x = jax.random.normal(jax.random.PRNGKey(0), (64, 256)) * 1e3
    d = quant.pairwise_sq_dists(x, x)          # true diagonal is exactly 0
    assert float(jnp.min(d)) >= 0.0
    # the raw matmul form really does go negative on this input — the
    # clamp is load-bearing, not decorative
    x2 = jnp.sum(x * x, -1, keepdims=True)
    raw = x2 - 2.0 * (x @ x.T) + jnp.sum(x * x, -1)[None, :]
    assert float(jnp.min(raw)) < 0.0


def test_kmeans_pp_seeding_survives_duplicate_heavy_data():
    """All-duplicate rows drive every d2 to ~0; categorical weights must
    stay finite (no log of a negative / NaN sampling distribution)."""
    x = jnp.full((128, 32), 500.0)
    cents = quant._kmeans_pp_init(jax.random.PRNGKey(0), x, 8)
    assert bool(jnp.all(jnp.isfinite(cents)))


def test_seeding_corpus_smaller_than_seed_batch():
    """Regression (satellite): n < seed_batch seeds on all points without
    replacement — the v0 `replace=n < m` guard was dead code."""
    x = jax.random.normal(jax.random.PRNGKey(0), (48, 8))
    cfg = quant.KMeansConfig(k=8, iters=5, seed_batch=4096, n_restarts=2)
    cents, mses = quant.kmeans_fit(jax.random.PRNGKey(1), x, cfg)
    assert cents.shape == (8, 8)
    assert bool(jnp.all(jnp.isfinite(cents)))
    assert float(quant.quantization_error(x, cents)) <= float(mses[0]) + 1e-6


def test_empty_cluster_repair_deterministic():
    """A centroid that captures zero points must re-seed on the farthest
    point instead of staying frozen at its stale position."""
    a = jnp.zeros((8, 2)) + jnp.arange(8)[:, None] * 0.01
    b = jnp.array([10.0, 0.0]) + jnp.arange(8)[:, None] * 0.01
    x = jnp.concatenate([a, b])
    c0 = jnp.array([[0.0, 0.0], [10.0, 0.0], [100.0, 100.0]])
    new_c, _ = quant._lloyd_step(x, c0)
    # the dead centroid moved onto an actual data point...
    assert bool(jnp.any(jnp.all(jnp.isclose(x, new_c[2][None], atol=1e-6),
                                axis=1)))
    # ...specifically the farthest-from-assigned one, not (100, 100)
    assert float(jnp.max(jnp.abs(new_c[2]))) < 20.0


def test_repair_recovers_all_clusters():
    """Refining from seeds that double-cover one cluster and leave one
    centroid dead must still end up covering every planted cluster."""
    key = jax.random.PRNGKey(7)
    centers = jnp.array([[0.0, 0.0], [10.0, 0.0], [0.0, 10.0]])
    idx = jax.random.randint(key, (300,), 0, 3)
    x = centers[idx] + 0.05 * jax.random.normal(jax.random.PRNGKey(8),
                                                (300, 2))
    c0 = jnp.array([[0.0, 0.1], [0.1, 0.0], [500.0, 500.0]])
    best_c, _, _ = quant.kmeans_refine(x, c0, iters=10)
    err = float(quant.quantization_error(x, best_c))
    assert err < 0.1, err  # dead centroid frozen at (500,500) would be ~33


def test_restart_selection_picks_lowest_inertia():
    """kmeans_fit must return exactly the restart `_fit_single` ranks best
    — and on a stuck-prone planted dataset the restarts genuinely differ."""
    key = jax.random.PRNGKey(0)
    # overclustered (48 prototypes >> k=16) + few iters: different seeds
    # genuinely land at different local minima
    centers = jax.random.normal(key, (48, 4)) * 5
    idx = jax.random.randint(jax.random.PRNGKey(1), (256,), 0, 48)
    x = centers[idx] + 0.05 * jax.random.normal(jax.random.PRNGKey(2),
                                                (256, 4))
    cfg = quant.KMeansConfig(k=16, iters=2, n_restarts=4)
    fit_key = jax.random.PRNGKey(3)
    cents, _ = quant.kmeans_fit(fit_key, x, cfg)
    e_best = float(quant.quantization_error(x, cents))
    finals = [float(quant._fit_single(kk, x, cfg)[2])
              for kk in jax.random.split(fit_key, 4)]
    assert e_best <= min(finals) + 1e-5
    assert max(finals) > min(finals)  # selection has something to select


def test_refine_returns_best_iterate_not_last(rng, monkeypatch):
    """Satellite: the fit must return the lowest-inertia iterate, not the
    last one. Force a strictly worsening trajectory and check the first
    iterate wins."""
    x = jax.random.normal(rng, (64, 4))
    c_good, _ = quant.kmeans_fit(rng, x, quant.KMeansConfig(k=8, iters=10,
                                                            n_restarts=1))
    monkeypatch.setattr(
        quant, "_lloyd_step",
        lambda xx, cc: (cc * 2.0 + 1.0, quant._inertia(xx, cc)))
    best_c, inertias, best_i = quant.kmeans_refine(x, c_good, iters=4)
    np.testing.assert_allclose(np.asarray(best_c), np.asarray(c_good))
    assert float(best_i) == float(inertias[0])


def test_minibatch_mode_recovers_planted_clusters(rng):
    centers = jax.random.normal(rng, (8, 8)) * 5
    idx = jax.random.randint(jax.random.PRNGKey(1), (2048,), 0, 8)
    x = centers[idx] + 0.05 * jax.random.normal(jax.random.PRNGKey(2),
                                                (2048, 8))
    cfg = quant.KMeansConfig(k=8, iters=40, minibatch=256, n_restarts=2)
    cents, _ = quant.kmeans_fit(rng, x, cfg)
    err = float(quant.quantization_error(x, cents))
    assert err < 0.2, err  # near the 8 * 0.05^2 noise floor


def test_sharded_kmeans_parity_on_1dev_mesh():
    """Satellite: the sharded trainer on a 1-device mesh must reproduce
    the single-host codebook (same seeds, psum over one shard)."""
    from repro.core import distributed as dist
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((1,), ("data",))
    x = jax.random.normal(jax.random.PRNGKey(0), (256, 8))
    cfg = quant.KMeansConfig(k=16, iters=8, n_restarts=2)
    c_sh, hist_sh = dist.sharded_kmeans_fit(mesh, jax.random.PRNGKey(3), x,
                                            cfg)
    c_ref, hist_ref = quant.kmeans_fit(jax.random.PRNGKey(3), x, cfg)
    np.testing.assert_allclose(np.asarray(c_sh), np.asarray(c_ref),
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(hist_sh), np.asarray(hist_ref),
                               atol=1e-5)


def test_pq_roundtrip(rng):
    x = jax.random.normal(rng, (256, 32))
    cbs = quant.pq_fit(rng, x, quant.PQConfig(k=16, n_sub=4, iters=8))
    assert cbs.shape == (4, 16, 8)
    codes = quant.pq_quantize(x, cbs)
    assert codes.shape == (256, 4)
    dec = quant.pq_decode(codes, cbs)
    assert dec.shape == x.shape
    # PQ with more subspaces should reconstruct better than K=16 flat
    flat_c, _ = quant.kmeans_fit(rng, x, quant.KMeansConfig(k=16, iters=8))
    pq_err = float(jnp.mean(jnp.sum((x - dec) ** 2, -1)))
    flat_err = float(quant.quantization_error(x, flat_c))
    assert pq_err < flat_err

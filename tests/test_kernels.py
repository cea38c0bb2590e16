"""Pallas kernel validation: interpret=True vs pure-jnp oracles, swept over
shapes and dtypes (deliverable (c) kernel requirement)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import quantization as quant
from repro.kernels import ops

SHAPES = [  # (B, Mq, D, N, Md)
    (1, 4, 16, 16, 8),
    (2, 8, 32, 48, 10),
    (3, 5, 64, 64, 17),   # odd Md, padding path
    (2, 16, 128, 32, 32),
]


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_maxsim_kernel(shape, dtype):
    b, mq, d, n, md = shape
    key = jax.random.PRNGKey(sum(shape))
    ks = jax.random.split(key, 4)
    q = jax.random.normal(ks[0], (b, mq, d), dtype)
    docs = jax.random.normal(ks[1], (n, md, d), dtype)
    qm = jax.random.uniform(ks[2], (b, mq)) > 0.2
    dm = jax.random.uniform(ks[3], (n, md)) > 0.2
    got = ops.maxsim(q, qm, docs, dm, impl="interpret", block_docs=16)
    want = ops.maxsim(q, qm, docs, dm, impl="ref")
    tol = 1e-4 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=tol,
                               rtol=tol)


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("k", [16, 256])
def test_quantized_maxsim_kernel(shape, k):
    b, mq, d, n, md = shape
    key = jax.random.PRNGKey(sum(shape) + k)
    ks = jax.random.split(key, 4)
    q = jax.random.normal(ks[0], (b, mq, d))
    cb = jax.random.normal(ks[1], (k, d))
    codes = jax.random.randint(ks[2], (n, md), 0, k)
    qm = jnp.ones((b, mq), bool)
    dm = jax.random.uniform(ks[3], (n, md)) > 0.2
    got = ops.quantized_maxsim(q, qm, codes, dm, cb, impl="interpret",
                               block_docs=16)
    want = ops.quantized_maxsim(q, qm, codes, dm, cb, impl="ref")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-4,
                               rtol=1e-4)


@pytest.mark.parametrize("bits", [4, 8, 9, 16])
@pytest.mark.parametrize("shape", SHAPES[:3])
def test_hamming_kernel(shape, bits):
    b, mq, d, n, md = shape
    key = jax.random.PRNGKey(bits)
    ks = jax.random.split(key, 4)
    qc = jax.random.randint(ks[0], (b, mq), 0, 2 ** bits)
    dc = jax.random.randint(ks[1], (n, md), 0, 2 ** bits)
    qm = jax.random.uniform(ks[2], (b, mq)) > 0.3
    dm = jax.random.uniform(ks[3], (n, md)) > 0.3
    got = ops.hamming_maxsim(qc, qm, dc, dm, bits=bits, impl="interpret",
                             block_docs=16)
    want = ops.hamming_maxsim(qc, qm, dc, dm, bits=bits, impl="ref")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=0)


@pytest.mark.parametrize("n,d,k", [(64, 16, 8), (100, 32, 16), (256, 128, 64),
                                   (130, 8, 4)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_kmeans_assign_kernel(n, d, k, dtype):
    key = jax.random.PRNGKey(n + d + k)
    x = jax.random.normal(key, (n, d), dtype)
    c = jax.random.normal(jax.random.PRNGKey(1), (k, d), dtype)
    got = ops.kmeans_assign(x, c, impl="interpret", block_n=32)
    want = ops.kmeans_assign(x, c, impl="ref")
    # bf16 ties can flip argmin; allow tiny disagreement for bf16
    agree = float(np.mean(np.asarray(got) == np.asarray(want)))
    assert agree >= (1.0 if dtype == jnp.float32 else 0.98)


def test_adc_table_split_is_exact():
    """The ADC kernel scores a bf16 one-hot against three bf16 terms of
    the f32 table; their sum must give back every f32 entry exactly."""
    from repro.kernels.quantized_maxsim import split_bf16
    x = jax.random.normal(jax.random.PRNGKey(3), (64, 256)) * jnp.logspace(
        -6, 3, 256)
    hi, mid, lo = jax.jit(split_bf16)(x)
    assert hi.dtype == mid.dtype == lo.dtype == jnp.bfloat16
    back = (hi.astype(jnp.float32) + mid.astype(jnp.float32)) \
        + lo.astype(jnp.float32)
    np.testing.assert_array_equal(np.asarray(back), np.asarray(x))


@pytest.mark.parametrize("b,k,mq,group", [
    (1, 16, 8, 1), (3, 16, 8, 3), (8, 16, 8, 8),
    (1, 256, 32, 1), (3, 256, 32, 3), (8, 256, 32, 8),
    (3, 16, 1200, 2),     # groups of 2 over 3 queries: one padded row
    (8, 16, 850, 3),      # groups of 3 over 8 queries: one padded row
])
def test_adc_kernel_batch_equals_per_query_calls(b, k, mq, group):
    """Queries scored in groups against one one-hot per (block, patch)
    give each query exactly what a call of its own gives, padded groups
    included; masked query tokens, masked patches and a page with every
    patch masked."""
    from repro.kernels import quantized_maxsim as qk
    md, n, tile = 8, 256, 128
    assert qk.query_group(b, mq, k, md, tile) == group
    ks = jax.random.split(jax.random.PRNGKey(b * mq + k), 4)
    table = jax.random.normal(ks[0], (b, mq, k)) * 10.0
    qm = (jax.random.uniform(ks[1], (b, mq)) > 0.25).astype(jnp.float32)
    codes = jax.random.randint(ks[2], (n, md), 0, k)
    dm = (jax.random.uniform(ks[3], (n, md)) > 0.3).at[5].set(False)
    dm = dm.astype(jnp.float32)
    got = qk.quantized_maxsim_pallas(table, qm, codes, dm, block_docs=tile,
                                     interpret=True)
    alone = [qk.quantized_maxsim_pallas(table[i:i + 1], qm[i:i + 1], codes,
                                        dm, block_docs=tile, interpret=True)
             for i in range(b)]
    assert np.array_equal(np.asarray(got), np.concatenate(alone))


def test_query_group_is_one_at_b1_and_fits_at_the_top_rung():
    from repro.kernels import quantized_maxsim as qk
    from repro.kernels import vmem
    for mq, k, md, tile in [(32, 256, 615, 256), (8, 16, 8, 128),
                            (32, 512, 128, 2048)]:
        assert qk.query_group(1, mq, k, md, tile) == 1
    g = qk.query_group(64, 32, 256, 615, 256)
    assert g == 32
    assert vmem.fits(qk.qmaxsim_vmem_bytes(256, 32, 256, 615, g))
    # one group of 64 does not fit, so the batch takes two of 32
    assert not vmem.fits(qk.qmaxsim_vmem_bytes(256, 32, 256, 615, 64))


def test_kernel_consistency_with_core_library(rng):
    """ops.quantized_maxsim (kernel path) == core.late_interaction ADC."""
    from repro.core import late_interaction as li
    ks = jax.random.split(rng, 3)
    q = jax.random.normal(ks[0], (2, 6, 16))
    docs = jax.random.normal(ks[1], (24, 9, 16))
    cb = jax.random.normal(ks[2], (32, 16))
    codes = quant.quantize(docs, cb)
    qm = jnp.ones((2, 6), bool)
    dm = jnp.ones((24, 9), bool)
    a = ops.quantized_maxsim(q, qm, codes, dm, cb, impl="interpret",
                             block_docs=8)
    b = li.quantized_maxsim(q, qm, codes, dm, cb)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)


# ---------------------------------------------------------------------------
# Streaming scan engine (core/scan.py): blocked score + top-k fusion
# ---------------------------------------------------------------------------

from repro.core import index as index_mod  # noqa: E402
from repro.core import late_interaction as li  # noqa: E402
from repro.core import scan as scan_mod  # noqa: E402

N_STREAM = 50  # deliberately not a multiple of any swept block size


def _adc_case(seed, n=N_STREAM, b=3, mq=5, d=16, md=7, k_cb=32):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    q = jax.random.normal(ks[0], (b, mq, d))
    cb = jax.random.normal(ks[1], (k_cb, d))
    codes = jax.random.randint(ks[2], (n, md), 0, k_cb)
    qm = jax.random.uniform(ks[3], (b, mq)) > 0.2
    dm = jax.random.uniform(ks[4], (n, md)) > 0.2
    dm = dm.at[:, 0].set(True)           # no accidental all-masked docs
    # plant an exact tie: docs 10 and 20 share codes AND mask
    codes = codes.at[20].set(codes[10])
    dm = dm.at[20].set(dm[10])
    return q, qm, codes, dm, cb


def _oracle_topk(scores, k):
    s, i = jax.lax.top_k(scores, k)
    return np.asarray(s), np.asarray(i)


@pytest.mark.parametrize("block", [1, 3, 7, 16, 50, 256])
def test_streaming_adc_blocked_equals_unblocked(block):
    """Blocked sweep == unblocked oracle (jnp impl), incl. ragged
    N % block tails and lowest-index tie-breaking. Ids must match
    exactly; scores are bit-exact per block but XLA may reassociate the
    Mq-sum across different block shapes, so the cross-block comparison
    allows ULP-level tolerance. A block covering the whole corpus is the
    single-block case and must be bit-exact end to end."""
    q, qm, codes, dm, cb = _adc_case(0)
    want_s, want_i = _oracle_topk(
        li.quantized_maxsim(q, qm, codes, dm, cb), k=10)
    got_s, got_i = scan_mod.quantized_maxsim_topk(
        q, qm, codes, dm, cb, k=10,
        scan=scan_mod.ScanConfig(block_docs=block, impl="jnp"))
    np.testing.assert_array_equal(np.asarray(got_i), want_i)
    if block >= N_STREAM:
        np.testing.assert_array_equal(np.asarray(got_s), want_s)
    else:
        np.testing.assert_allclose(np.asarray(got_s), want_s,
                                   atol=1e-5, rtol=1e-6)


@pytest.mark.parametrize("block", [7, 16, 50])
def test_streaming_adc_interpret_parity(block):
    """The Pallas block scorer (interpret mode) matches the jnp engine
    up to merge-order tolerance; ids agree exactly."""
    q, qm, codes, dm, cb = _adc_case(1)
    ref_s, ref_i = scan_mod.quantized_maxsim_topk(
        q, qm, codes, dm, cb, k=10,
        scan=scan_mod.ScanConfig(block_docs=block, impl="jnp"))
    got_s, got_i = scan_mod.quantized_maxsim_topk(
        q, qm, codes, dm, cb, k=10,
        scan=scan_mod.ScanConfig(block_docs=block, impl="interpret"))
    np.testing.assert_array_equal(np.asarray(got_i), np.asarray(ref_i))
    np.testing.assert_allclose(np.asarray(got_s), np.asarray(ref_s),
                               atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("impl", ["jnp", "interpret"])
def test_streaming_float_blocked_equals_unblocked(impl):
    ks = jax.random.split(jax.random.PRNGKey(2), 4)
    q = jax.random.normal(ks[0], (2, 6, 16))
    docs = jax.random.normal(ks[1], (37, 9, 16))
    qm = jax.random.uniform(ks[2], (2, 6)) > 0.2
    dm = jax.random.uniform(ks[3], (37, 9)) > 0.2
    dm = dm.at[:, 0].set(True)
    want_s, want_i = _oracle_topk(li.maxsim(q, qm, docs, dm), k=8)
    got_s, got_i = scan_mod.maxsim_topk(
        q, qm, docs, dm, k=8, scan=scan_mod.ScanConfig(16, impl))
    np.testing.assert_array_equal(np.asarray(got_i), want_i)
    # XLA may reassociate the per-doc reductions across block shapes:
    # ULP-level tolerance, as in the ADC sweep above
    atol, rtol = (1e-5, 1e-6) if impl == "jnp" else (1e-4, 1e-4)
    np.testing.assert_allclose(np.asarray(got_s), want_s, atol=atol,
                               rtol=rtol)


@pytest.mark.parametrize("impl", ["jnp", "interpret"])
def test_streaming_hamming_blocked_equals_unblocked(impl):
    ks = jax.random.split(jax.random.PRNGKey(3), 4)
    bits = 5
    qc = jax.random.randint(ks[0], (2, 6), 0, 2 ** bits)
    dc = jax.random.randint(ks[1], (41, 9), 0, 2 ** bits)
    qm = jax.random.uniform(ks[2], (2, 6)) > 0.3
    dm = jax.random.uniform(ks[3], (41, 9)) > 0.3
    dm = dm.at[:, 0].set(True)
    want_s, want_i = _oracle_topk(
        li.binary_maxsim(qc, qm, dc, dm, bits), k=8)
    got_s, got_i = scan_mod.hamming_maxsim_topk(
        qc, qm, dc, dm, bits=bits, k=8, scan=scan_mod.ScanConfig(16, impl))
    # integer scores tie freely: require the scores bit-equal and the ids
    # equal (blocks sweep in doc order, so ties still break lowest-first);
    # dtype is int32 on every impl (the pallas f32 output is cast back)
    assert got_s.dtype == jnp.int32
    np.testing.assert_array_equal(np.asarray(got_s), want_s)
    np.testing.assert_array_equal(np.asarray(got_i), want_i)


def test_streaming_all_masked_docs_match_oracle():
    """Docs with every patch masked still surface (hugely negative but
    finite scores) exactly as the unblocked oracle ranks them."""
    q, qm, codes, dm, cb = _adc_case(4, n=12)
    dm = dm.at[3].set(False).at[11].set(False)
    want_s, want_i = _oracle_topk(
        li.quantized_maxsim(q, qm, codes, dm, cb), k=12)
    got_s, got_i = scan_mod.quantized_maxsim_topk(
        q, qm, codes, dm, cb, k=12, scan=scan_mod.ScanConfig(5, "jnp"))
    np.testing.assert_array_equal(np.asarray(got_i), want_i)
    np.testing.assert_allclose(np.asarray(got_s), want_s, rtol=1e-6)
    assert set(np.asarray(got_i)[0]) == set(range(12))  # nobody dropped


def test_streaming_valid_mask_and_sentinel_tail():
    """valid=False rows score NEG_INF with id -1; k beyond the valid pool
    fills with the sub-NEG_INF sentinel instead of crashing."""
    q, qm, codes, dm, cb = _adc_case(5, n=8)
    valid = jnp.array([True, False] * 4)
    got_s, got_i = scan_mod.quantized_maxsim_topk(
        q, qm, codes, dm, cb, k=8, valid=valid,
        scan=scan_mod.ScanConfig(3, "jnp"))
    got_s, got_i = np.asarray(got_s), np.asarray(got_i)
    assert set(got_i[0, :4]) == {0, 2, 4, 6}       # valid docs first
    np.testing.assert_array_equal(got_i[:, 4:], -1)
    assert np.all(got_s[:, 4:] <= li.NEG_INF)


def test_streaming_per_query_candidates_match_vmapped_oracle():
    """ivf/hnsw/rerank layout: (B, P, Md) per-query pools, bit-exact."""
    b, p, md, k_cb, mq, d = 3, 11, 6, 16, 4, 8
    ks = jax.random.split(jax.random.PRNGKey(6), 6)
    q = jax.random.normal(ks[0], (b, mq, d))
    cb = jax.random.normal(ks[1], (k_cb, d))
    codes = jax.random.randint(ks[2], (b, p, md), 0, k_cb)
    qm = jnp.ones((b, mq), bool)
    dm = jax.random.uniform(ks[3], (b, p, md)) > 0.2
    dm = dm.at[..., 0].set(True)
    ids = jax.random.permutation(ks[4], 100)[:b * p].reshape(b, p)
    valid = jax.random.uniform(ks[5], (b, p)) > 0.2

    def oracle_one(qi, qmi, c, m, v, idr):
        s = li.quantized_maxsim(qi[None], qmi[None], c, m, cb)[0]
        s = jnp.where(v, s, li.NEG_INF)
        top_s, top_j = jax.lax.top_k(s, 5)
        return top_s, jnp.where(top_s > li.NEG_INF, idr[top_j], -1)

    want_s, want_i = jax.vmap(oracle_one)(q, qm, codes, dm, valid, ids)
    got_s, got_i = scan_mod.quantized_maxsim_topk(
        q, qm, codes, dm, cb, k=5, doc_ids=ids, valid=valid,
        scan=scan_mod.ScanConfig(4, "jnp"))
    # scores to ULP tolerance (XLA reassociates the Mq-sum per block
    # shape); ids exact
    np.testing.assert_allclose(np.asarray(got_s), np.asarray(want_s),
                               atol=1e-5, rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(got_i), np.asarray(want_i))


def test_streaming_per_query_interpret_parity():
    b, p, md, k_cb, mq, d = 2, 9, 6, 16, 4, 8
    ks = jax.random.split(jax.random.PRNGKey(7), 4)
    q = jax.random.normal(ks[0], (b, mq, d))
    cb = jax.random.normal(ks[1], (k_cb, d))
    codes = jax.random.randint(ks[2], (b, p, md), 0, k_cb)
    qm = jnp.ones((b, mq), bool)
    dm = jax.random.uniform(ks[3], (b, p, md)) > 0.2
    dm = dm.at[..., 0].set(True)
    ref_s, ref_i = scan_mod.quantized_maxsim_topk(
        q, qm, codes, dm, cb, k=4, scan=scan_mod.ScanConfig(4, "jnp"))
    got_s, got_i = scan_mod.quantized_maxsim_topk(
        q, qm, codes, dm, cb, k=4, scan=scan_mod.ScanConfig(4, "interpret"))
    np.testing.assert_array_equal(np.asarray(got_i), np.asarray(ref_i))
    np.testing.assert_allclose(np.asarray(got_s), np.asarray(ref_s),
                               atol=1e-4, rtol=1e-4)


# --- k > N sentinel regression (the lax.top_k crash bugfix) ----------------

def _tiny_flat_index(seed, n=5, md=4, k_cb=8, d=8):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    cb = jax.random.normal(ks[0], (k_cb, d))
    codes = jax.random.randint(ks[1], (n, md), 0, k_cb).astype(jnp.uint8)
    mask = jnp.ones((n, md), bool)
    q = jax.random.normal(ks[2], (2, 3, d))
    qm = jnp.ones((2, 3), bool)
    return q, qm, codes, mask, cb


def test_search_flat_k_exceeds_corpus():
    q, qm, codes, mask, cb = _tiny_flat_index(0)
    ix = index_mod.build_flat(codes, mask, cb)
    s, i = index_mod.search_flat(ix, q, qm, k=9)      # v0: top_k crash
    s, i = np.asarray(s), np.asarray(i)
    assert i.shape == (2, 9)
    want_s, want_i = _oracle_topk(
        li.quantized_maxsim(q, qm, codes, mask, cb), k=5)
    np.testing.assert_array_equal(i[:, :5], want_i)
    np.testing.assert_array_equal(s[:, :5], want_s)
    np.testing.assert_array_equal(i[:, 5:], -1)
    assert np.all(s[:, 5:] <= li.NEG_INF)


def test_search_float_flat_k_exceeds_corpus():
    ks = jax.random.split(jax.random.PRNGKey(1), 2)
    docs = jax.random.normal(ks[0], (4, 3, 8))
    mask = jnp.ones((4, 3), bool)
    q = jax.random.normal(ks[1], (2, 3, 8))
    qm = jnp.ones((2, 3), bool)
    ix = index_mod.build_float_flat(docs, mask)
    s, i = index_mod.search_float_flat(ix, q, qm, k=7)
    s, i = np.asarray(s), np.asarray(i)
    want_s, want_i = _oracle_topk(li.maxsim(q, qm, docs, mask), k=4)
    np.testing.assert_array_equal(i[:, :4], want_i)
    np.testing.assert_allclose(s[:, :4], want_s, atol=1e-5, rtol=1e-6)
    np.testing.assert_array_equal(i[:, 4:], -1)
    assert np.all(s[:, 4:] <= li.NEG_INF)


def test_search_hamming_k_exceeds_corpus():
    bits = 4
    ks = jax.random.split(jax.random.PRNGKey(2), 2)
    dc = jax.random.randint(ks[0], (5, 4), 0, 2 ** bits)
    mask = jnp.ones((5, 4), bool)
    qc = jax.random.randint(ks[1], (2, 3), 0, 2 ** bits)
    qm = jnp.ones((2, 3), bool)
    ix = index_mod.build_hamming(dc, mask, bits)
    s, i = index_mod.search_hamming(ix, qc, qm, bits=bits, k=8)
    s, i = np.asarray(s), np.asarray(i)
    assert i.shape == (2, 8)
    want_s, want_i = _oracle_topk(
        li.binary_maxsim(qc, qm, ix.codes, mask, bits), k=5)
    np.testing.assert_array_equal(i[:, :5], want_i)
    np.testing.assert_array_equal(s[:, :5], want_s)
    np.testing.assert_array_equal(i[:, 5:], -1)
    assert np.all(s[:, 5:] == np.iinfo(np.int32).min)


# --- memory regression: the scan must never build O(N*Mq) ------------------

def test_streaming_scan_never_materializes_corpus_scores():
    """Acceptance: at N = 2**20 the old unblocked path's similarity
    tensor alone would be B*Mq*N*Md*4 = 2.1 GB; the streaming scan's
    budget is gated by the `search_flat` manifest (jaxpr shape
    inspection in repro.analysis), and a large-N CPU run must actually
    complete."""
    from repro.analysis import analyze_manifest, get_manifest

    m = get_manifest("search_flat")
    old_sim_bytes = 8 * 8 * m.n * 16 * 4
    assert old_sim_bytes > 30 * m.max_block_bytes
    violations = analyze_manifest(m)
    assert violations == [], [str(v) for v in violations]

    # live run at an N where the unblocked similarity tensor (~128 MB at
    # these shapes x ~4 batch copies in flight) would dwarf the blocked
    # path's footprint; plant a known best doc and retrieve it
    n_live, md, d, k_cb = 1 << 17, 16, 16, 16
    ks = jax.random.split(jax.random.PRNGKey(8), 2)
    cb = jax.random.normal(ks[0], (k_cb, d))
    cb = cb.at[3].mul(10.0)                     # self-dot dominates
    # random docs draw from every code EXCEPT 3 — only the planted doc
    # holds the loud centroid, so its top-1 win is untied
    codes = jax.random.randint(ks[1], (n_live, md), 0, k_cb - 1)
    codes = jnp.where(codes >= 3, codes + 1, codes)
    codes = codes.at[77777].set(3).astype(jnp.uint8)
    ix = index_mod.build_flat(codes.astype(jnp.uint8),
                              jnp.ones((n_live, md), bool), cb)
    q = jnp.tile(cb[3][None, None], (1, 4, 1))   # query = loud centroid
    qm = jnp.ones((1, 4), bool)
    s, i = index_mod.search_flat(ix, q, qm, k=3,
                                 scan=scan_mod.ScanConfig(512, "jnp"))
    assert int(np.asarray(i)[0, 0]) == 77777

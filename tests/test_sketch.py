"""Property tests for the count-sketch (capability parity with csvec
CSVec; reference usage CommEfficient/fed_worker.py:312-320,
fed_aggregator.py:584-595). Linearity and heavy-hitter recovery are the
load-bearing properties of FetchSGD."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from commefficient_tpu.ops.sketch import CSVec


def make_sketch(d=1000, c=200, r=5, num_blocks=3):
    return CSVec(d=d, c=c, r=r, num_blocks=num_blocks)


def test_linearity():
    s = make_sketch()
    rng = np.random.RandomState(1)
    a = jnp.asarray(rng.randn(s.d).astype(np.float32))
    b = jnp.asarray(rng.randn(s.d).astype(np.float32))
    t = s.encode(a) + s.encode(b)
    np.testing.assert_allclose(t, s.encode(a + b), rtol=1e-5, atol=1e-5)


def test_num_blocks_is_pure_scheduling():
    # csvec's numBlocks changes hashing; ours must NOT change results.
    rng = np.random.RandomState(2)
    v = jnp.asarray(rng.randn(1000).astype(np.float32))
    t1 = CSVec(d=1000, c=300, r=3, num_blocks=1).encode(v)
    t7 = CSVec(d=1000, c=300, r=3, num_blocks=7).encode(v)
    np.testing.assert_allclose(t1, t7, rtol=1e-6, atol=1e-6)


def test_exact_recovery_sparse_vector():
    # k-sparse vector, c >> k: unsketch must recover it exactly.
    s = CSVec(d=5000, c=1000, r=5, num_blocks=4)
    v = np.zeros(s.d, np.float32)
    hot = np.array([7, 123, 999, 2500, 4999])
    v[hot] = np.array([10.0, -8.0, 6.0, -12.0, 9.0], np.float32)
    out = np.asarray(s.decode_topk(s.encode(jnp.asarray(v)), k=5))
    np.testing.assert_allclose(out, v, atol=1e-4)


def test_heavy_hitter_recovery_with_noise():
    # heavy hitters on top of dense noise: top-k must find the hitters
    # and estimate them within the noise floor.
    s = CSVec(d=20000, c=5000, r=5, num_blocks=5)
    rng = np.random.RandomState(3)
    v = rng.randn(s.d).astype(np.float32) * 0.01
    hot = rng.choice(s.d, 20, replace=False)
    v[hot] = rng.choice([-1.0, 1.0], 20) * (5.0 + rng.rand(20))
    out = np.asarray(s.decode_topk(s.encode(jnp.asarray(v)), k=20))
    found = np.nonzero(out)[0]
    assert set(hot).issubset(set(found))
    np.testing.assert_allclose(out[hot], v[hot], atol=0.5)


def test_encode_sparse_matches_dense():
    s = make_sketch(d=500, c=100, r=3, num_blocks=2)
    idx = jnp.array([3, 77, 499, 500], jnp.int32)  # 500 is out of range
    vals = jnp.array([1.0, -2.0, 3.0, 99.0])
    dense = jnp.zeros(s.d).at[idx[:3]].set(vals[:3])
    np.testing.assert_allclose(
        s.encode_sparse(idx, vals), s.encode(dense), rtol=1e-5, atol=1e-5)


def test_encode_k_sparse_routes_agree():
    # encode_k_sparse must equal encode_sparse whichever route the
    # geometry/backend heuristic picks (on the CPU test backend it
    # always scatters; the dense route's equality is the linearity
    # property asserted above — here we pin the dispatcher itself,
    # including the caller-supplied `dense` form)
    s = make_sketch(d=500, c=100, r=3, num_blocks=2)
    idx = jnp.array([3, 77, 499, 500], jnp.int32)
    vals = jnp.array([1.0, -2.0, 3.0, 99.0])
    dense = jnp.zeros(s.d).at[idx[:3]].set(vals[:3])
    want = np.asarray(s.encode_sparse(idx, vals))
    np.testing.assert_allclose(
        s.encode_k_sparse(idx, vals), want, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(
        s.encode_k_sparse(idx, vals, dense=dense), want,
        rtol=1e-5, atol=1e-5)
    # and the dense route explicitly (what a big-k TPU run executes)
    np.testing.assert_allclose(
        s.encode(dense), want, rtol=1e-5, atol=1e-5)


def test_threshold_decode_matches_exact_at_full_sample(monkeypatch):
    # with stride 1 (sample = full vector) the threshold route's
    # selection IS the exact top-k (CPU approx_max_k is exact), so
    # decode_topk_dense must equal decode_topk coordinate for
    # coordinate
    import commefficient_tpu.ops.sketch as sketch_mod
    monkeypatch.setattr(sketch_mod, "THRESHOLD_DECODE_MIN_D", 1000)
    s = CSVec(d=20000, c=5000, r=5, num_blocks=4)
    assert s._threshold_decode
    rng = np.random.RandomState(7)
    v = jnp.asarray(rng.randn(s.d).astype(np.float32))
    t = s.encode(v)
    np.testing.assert_allclose(
        s.decode_topk_dense(t, k=500), s.decode_topk(t, k=500),
        rtol=1e-6, atol=1e-6)


def test_threshold_decode_sampled(monkeypatch):
    # with a real subsample the selected count must land near k and
    # the unambiguous heavy hitters must all be selected
    import commefficient_tpu.ops.sketch as sketch_mod
    monkeypatch.setattr(sketch_mod, "THRESHOLD_DECODE_MIN_D", 1000)
    import commefficient_tpu.ops.flat as flat_mod
    monkeypatch.setattr(flat_mod, "_TOPK_SAMPLE", 4096)
    s = CSVec(d=40000, c=10000, r=5, num_blocks=4)
    rng = np.random.RandomState(8)
    v = rng.randn(s.d).astype(np.float32) * 0.01
    hot = rng.choice(s.d, 50, replace=False)
    v[hot] = rng.choice([-1.0, 1.0], 50) * (5.0 + rng.rand(50))
    k = 2000
    out = np.asarray(s.decode_topk_dense(s.encode(jnp.asarray(v)), k=k))
    nz = np.nonzero(out)[0]
    assert set(hot).issubset(set(nz))
    # sampling noise on the count: ks = k*4096/40000 ~ 205 samples;
    # binomial spread ~ 1/sqrt(205) ~ 7% -> generous 25% band
    assert 0.75 * k <= len(nz) <= 1.25 * k, len(nz)


def test_threshold_decode_sparser_than_k(monkeypatch):
    # fewer than k nonzero estimates: thr hits 0 and the guard must
    # select exactly the nonzero estimates, not everything
    import commefficient_tpu.ops.sketch as sketch_mod
    monkeypatch.setattr(sketch_mod, "THRESHOLD_DECODE_MIN_D", 100)
    s = CSVec(d=5000, c=1000, r=5, num_blocks=4)
    v = np.zeros(s.d, np.float32)
    hot = np.array([7, 123, 999, 2500, 4999])
    v[hot] = np.array([10.0, -8.0, 6.0, -12.0, 9.0], np.float32)
    out = np.asarray(s.decode_topk_dense(s.encode(jnp.asarray(v)),
                                         k=500))
    np.testing.assert_allclose(out, v, atol=1e-4)
    # nothing beyond the five true coordinates may be selected: a
    # 5-sparse vector into c=1000 buckets leaves most buckets empty,
    # so most estimates are exactly zero
    assert len(np.nonzero(out)[0]) <= 5 * s.r


# ---------------------------------------------------------------------------
# the routes of one geometry agree: static unroll against the scan
# above STATIC_UNROLL_LIMIT, materialised against blockwise decode,
# and each against the hash that defines the sketch

GEOMETRIES = [
    dict(d=1000, c=200, r=5, num_blocks=3),   # padded tail, odd r
    dict(d=512, c=128, r=4, num_blocks=1),    # exact fit, even r
    dict(d=300, c=400, r=3, num_blocks=2),    # single chunk, c > d
]
geometries = pytest.mark.parametrize(
    "geom", GEOMETRIES, ids=["padded", "exact", "one_chunk"])


def _vec(d, seed):
    return np.random.RandomState(seed).randn(d).astype(np.float32)


def _both_routes(monkeypatch, s, fn):
    """(fn() with sketch `s` on the static route, fn() on the scan)."""
    import commefficient_tpu.ops.sketch as sketch_mod
    assert s._static_path
    static = fn()
    monkeypatch.setattr(sketch_mod, "STATIC_UNROLL_LIMIT", 0)
    assert not s._static_path
    return static, fn()


@geometries
def test_encode_static_matches_scan(geom, monkeypatch):
    s = CSVec(**geom)
    v = jnp.asarray(_vec(s.d, 1))
    static, scan = _both_routes(
        monkeypatch, s, lambda: np.asarray(s.encode(v)))
    # the same terms summed chunk by chunk in both: a few ulp apart
    np.testing.assert_allclose(static, scan, rtol=1e-6, atol=1e-6)


@geometries
def test_encode_matches_hash_definition(geom):
    # table[j, bucket_j(i)] += sign_j(i) * v[i], coordinate by
    # coordinate, in float64: what encode's rotations must amount to
    s = CSVec(**geom)
    v = _vec(s.d, 2)
    buckets, signs = (np.asarray(a) for a in
                      s.hash_indices(jnp.arange(s.d, dtype=jnp.int32)))
    want = np.zeros(s.table_shape, np.float64)
    for j in range(s.r):
        np.add.at(want[j], buckets[j], signs[j].astype(np.float64) * v)
    np.testing.assert_allclose(np.asarray(s.encode(jnp.asarray(v))), want,
                               rtol=1e-5, atol=1e-5)


@geometries
def test_estimate_all_static_matches_scan(geom, monkeypatch):
    s = CSVec(**geom)
    t = s.encode(jnp.asarray(_vec(s.d, 3)))
    static, scan = _both_routes(
        monkeypatch, s, lambda: np.asarray(s.estimate_all(t)))
    assert static.shape == (s.n_chunks, s.c)
    # un-rotation, a sign and a median: no sum, so bit for bit
    np.testing.assert_array_equal(static, scan)


@geometries
def test_estimate_all_matches_per_coordinate_estimate(geom):
    s = CSVec(**geom)
    t = s.encode(jnp.asarray(_vec(s.d, 4)))
    np.testing.assert_array_equal(
        np.asarray(s.estimate_all(t)).reshape(-1)[: s.d],
        np.asarray(s.estimate(t, jnp.arange(s.d))))


def test_zero_offsets_rotate_by_a_whole_row(monkeypatch):
    # offset 0 is the boundary of both rotations: the scan's _rotate
    # slices the doubled row at c - 0 == c, its last legal start, and
    # _unrotate at 0. Every offset forced to 0, so the boundary is hit
    # on purpose and not left to the seed's draws; the table is then
    # the signed sum of the chunks, which numpy can say directly.
    s = CSVec(d=600, c=128, r=3, num_blocks=1)
    object.__setattr__(s, "_offsets", np.zeros_like(s._offsets))
    v = _vec(s.d, 11)
    chunks = np.pad(v, (0, s.n_chunks * s.c - s.d)).reshape(-1, s.c)
    want = np.einsum("rb,rc,bc->rc", s._delta, s._eps, chunks)

    def both():
        t = s.encode(jnp.asarray(v))
        return np.asarray(t), np.asarray(s.estimate_all(t))

    (t_static, e_static), (t_scan, e_scan) = _both_routes(
        monkeypatch, s, both)
    np.testing.assert_allclose(t_static, want, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(t_scan, want, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(e_static, e_scan, rtol=1e-6, atol=1e-6)


def test_decode_topk_sparse_materialized_matches_blockwise(monkeypatch):
    # planted heavy hitters, several in one chunk: the blockwise route
    # (per-chunk candidates, then one top-k over the survivors) must
    # return what the single select over all estimates returns
    s = CSVec(d=5000, c=1000, r=5, num_blocks=4)
    rng = np.random.RandomState(3)
    v = np.zeros(s.d, np.float32)
    hot = np.concatenate([rng.choice(1000, 8, replace=False),
                          1000 + rng.choice(4000, 12, replace=False)])
    v[hot] = rng.choice([-1.0, 1.0], 20) * (5.0 + np.arange(20))
    t = s.encode(jnp.asarray(v))

    def decode():
        idx, vals = (np.asarray(a) for a in s.decode_topk_sparse(t, k=20))
        order = np.argsort(idx)
        return idx[order], vals[order]

    (i_mat, v_mat), (i_blk, v_blk) = _both_routes(monkeypatch, s, decode)
    np.testing.assert_array_equal(i_mat, np.sort(hot))
    np.testing.assert_array_equal(i_mat, i_blk)
    np.testing.assert_array_equal(v_mat, v_blk)


def test_threshold_decode_over_scan_estimates(monkeypatch):
    # decode_topk_dense's own gate, lowered, on a sketch whose
    # estimates come from the scan: a 10-sparse vector decodes exactly
    # (the threshold floors at f32-tiny and keeps just the nonzeros)
    import commefficient_tpu.ops.sketch as sketch_mod
    monkeypatch.setattr(sketch_mod, "THRESHOLD_DECODE_MIN_D", 1000)
    monkeypatch.setattr(sketch_mod, "STATIC_UNROLL_LIMIT", 0)
    s = CSVec(d=20000, c=5000, r=5, num_blocks=4)
    assert s._threshold_decode and not s._static_path
    rng = np.random.RandomState(9)
    v = np.zeros(s.d, np.float32)
    hot = rng.choice(s.d, 10, replace=False)
    v[hot] = rng.choice([-1.0, 1.0], 10) * (5.0 + rng.rand(10))
    out = np.asarray(s.decode_topk_dense(s.encode(jnp.asarray(v)), k=10))
    np.testing.assert_allclose(out, v, atol=1e-4)


def test_threshold_decode_chunk_narrower_than_stride(monkeypatch):
    # the sample strides over the flat estimates, not over chunks: at
    # a stride of two chunks every sample comes from another chunk and
    # half the chunks give none, and the heavy hitters still come back
    import commefficient_tpu.ops.flat as flat_mod
    import commefficient_tpu.ops.sketch as sketch_mod
    monkeypatch.setattr(sketch_mod, "THRESHOLD_DECODE_MIN_D", 1000)
    monkeypatch.setattr(flat_mod, "_TOPK_SAMPLE", 8)
    s = CSVec(d=4096, c=256, r=5, num_blocks=1)
    assert s._threshold_decode and s.d // 8 == 2 * s.c
    v = np.zeros(s.d, np.float32)
    hot = [5, 900, 3500]
    v[hot] = [7.0, -6.0, 5.0]
    # jitted: one compile in place of one per static shift
    out = np.asarray(jax.jit(
        lambda x: s.decode_topk_dense(s.encode(x), k=3))(jnp.asarray(v)))
    np.testing.assert_allclose(out, v, atol=1e-4)


def test_l2estimate():
    s = CSVec(d=10000, c=5000, r=5, num_blocks=4)
    rng = np.random.RandomState(4)
    v = jnp.asarray(rng.randn(s.d).astype(np.float32))
    est = float(s.l2estimate(s.encode(v)))
    true = float(jnp.linalg.norm(v))
    assert abs(est - true) / true < 0.15


def test_estimate_unbiased_single_coord():
    s = CSVec(d=100, c=1000, r=5, num_blocks=1)
    v = jnp.zeros(s.d).at[42].set(7.0)
    est = s.estimate(s.encode(v), jnp.array([42]))
    np.testing.assert_allclose(est, [7.0], atol=1e-5)


def test_decode_topk_sparse_padding_index():
    # fewer than k nonzeros: unfilled slots must carry index d.
    s = CSVec(d=100, c=200, r=3, num_blocks=1)
    v = jnp.zeros(s.d).at[5].set(3.0)
    idx, vals = s.decode_topk_sparse(s.encode(v), k=4)
    idx, vals = np.asarray(idx), np.asarray(vals)
    assert 5 in idx
    # padding entries are (d, ~0)
    pad = idx != 5
    assert np.all(np.abs(vals[pad]) < 1e-5)
    dense = np.asarray(s.decode_topk(s.encode(v), k=4))
    np.testing.assert_allclose(dense[5], 3.0, atol=1e-5)
    assert np.count_nonzero(np.abs(dense) > 1e-5) == 1


def test_sketch_jits_and_psum_linearity(mesh):
    """The FetchSGD payoff: psum of per-shard tables == sketch of the
    summed vector (replaces the reference's NCCL reduce of tables,
    fed_worker.py:138)."""
    from jax.sharding import PartitionSpec as P

    from jax import shard_map

    s = CSVec(d=256, c=64, r=3, num_blocks=2)
    n = len(jax.devices())
    vecs = jax.random.normal(jax.random.PRNGKey(0), (n, s.d))

    @jax.jit
    def summed_table(vs):
        def f(v):
            return jax.lax.psum(s.encode(v[0]), "clients")
        return shard_map(
            f, mesh=mesh, in_specs=P("clients"), out_specs=P())(vs)

    np.testing.assert_allclose(
        summed_table(vecs), s.encode(vecs.sum(0)), rtol=1e-4, atol=1e-4)

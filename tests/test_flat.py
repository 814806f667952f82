"""Golden tests for the flat-vector substrate (reference semantics:
CommEfficient/utils.py:232-313)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from commefficient_tpu.ops import flat


def test_masked_topk_1d():
    v = jnp.array([0.1, -5.0, 3.0, 0.0, -0.2, 4.0])
    out = flat.masked_topk(v, 2)
    np.testing.assert_allclose(out, [0, -5.0, 0, 0, 0, 4.0])


def test_masked_topk_2d_per_row():
    v = jnp.array([[1.0, -3.0, 2.0], [5.0, 0.5, -0.1]])
    out = flat.masked_topk(v, 1)
    np.testing.assert_allclose(out, [[0, -3.0, 0], [5.0, 0, 0]])


def test_masked_topk_matches_sort():
    rng = np.random.RandomState(0)
    v = jnp.asarray(rng.randn(257).astype(np.float32))
    k = 31
    out = np.asarray(flat.masked_topk(v, k))
    idx = np.argsort(np.asarray(v) ** 2)[-k:]
    expected = np.zeros_like(v)
    expected[idx] = np.asarray(v)[idx]
    np.testing.assert_allclose(out, expected)


def test_masked_topk_threshold_matches_exact_at_full_sample(monkeypatch):
    # with stride 1 the threshold route's selection IS the exact top-k
    # (CPU approx_max_k is exact): above-gate masked_topk must equal
    # the exact route coordinate for coordinate, 1-D and 2-D
    monkeypatch.setattr(flat, "TOPK_THRESHOLD_MIN_D", 100)
    rng = np.random.RandomState(5)
    v = jnp.asarray(rng.randn(4, 3000).astype(np.float32))
    k = 100
    got = np.asarray(flat.masked_topk(v, k))
    want = np.asarray(jax.vmap(lambda r: flat._topk_exact_1d(r, k))(v))
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(
        flat.masked_topk(v[0], k), want[0], rtol=1e-6, atol=1e-6)


def test_masked_topk_threshold_sampled(monkeypatch):
    # real subsample: count near k, unambiguous heavy hitters all kept
    monkeypatch.setattr(flat, "TOPK_THRESHOLD_MIN_D", 1000)
    monkeypatch.setattr(flat, "_TOPK_SAMPLE", 4096)
    rng = np.random.RandomState(6)
    d, k = 40000, 2000
    v = rng.randn(d).astype(np.float32) * 0.01
    hot = rng.choice(d, 50, replace=False)
    v[hot] = rng.choice([-1.0, 1.0], 50) * (5.0 + rng.rand(50))
    out = np.asarray(flat.masked_topk(jnp.asarray(v), k))
    nz = np.nonzero(out)[0]
    assert set(hot).issubset(set(nz))
    assert 0.75 * k <= len(nz) <= 1.25 * k, len(nz)
    np.testing.assert_allclose(out[nz], v[nz])


def _parent_sampled_threshold_mask(v, k, sample):
    """The definition `sampled_threshold_mask` had before its sample
    became a `lax.slice`, in numpy: every `stride`-th square, the
    `ks`-th largest of them floored at f32-tiny, `sq >= thr`."""
    d = v.shape[0]
    k = min(k, d)
    sq = v * v
    picked = sq[::max(1, d // sample)]
    n = picked.shape[0]
    ks = max(1, min(int(round(k * n / d)), n))
    thr = max(np.sort(picked)[n - ks], np.finfo(np.float32).tiny)
    return np.where(sq >= thr, v, np.float32(0.0))


@pytest.mark.parametrize("batched", [False, True], ids=["1d", "vmapped"])
@pytest.mark.parametrize("d, sample", [
    (4096, 1024),      # d a multiple of the stride (4)
    (4099, 1024),      # not one: the last stride is short
    (6001, 1000),      # stride 6, as at the benchmark's D: 1,001 samples
    (3000, 4096),      # stride 1: the sample is the vector
], ids=["multiple", "ragged", "stride6", "stride1"])
def test_sampled_threshold_mask_is_the_strided_sample_bit_for_bit(
        monkeypatch, batched, d, sample):
    # the sample is read by lax.slice (a jnp `sq[::stride]` traces to
    # a gather on jax 0.9.0): same coordinates, same squares, same
    # selection, to the bit (CPU approx_max_k is exact)
    monkeypatch.setattr(flat, "_TOPK_SAMPLE", sample)
    rng = np.random.RandomState(d)
    v = (rng.standard_cauchy((3, d)) * 0.01).astype(np.float32)
    k = d // 20

    def one(r):
        return flat.sampled_threshold_mask(r, k)

    rows = jnp.asarray(v)
    got = jax.vmap(one)(rows) if batched else jnp.stack(
        [one(r) for r in rows])
    want = np.stack([_parent_sampled_threshold_mask(r, k, sample)
                     for r in v])
    np.testing.assert_array_equal(np.asarray(got), want)
    assert 0 < np.count_nonzero(want) < want.size


def test_masked_topk_threshold_sparser_than_k(monkeypatch):
    # fewer than k nonzeros: the tiny floor keeps selection to exactly
    # the nonzeros instead of everything
    monkeypatch.setattr(flat, "TOPK_THRESHOLD_MIN_D", 100)
    v = np.zeros(5000, np.float32)
    v[[3, 1000, 4999]] = [2.0, -7.0, 0.5]
    out = np.asarray(flat.masked_topk(jnp.asarray(v), 500))
    np.testing.assert_allclose(out, v)


def test_clip_to_l2_noop_below_threshold():
    v = jnp.array([0.3, 0.4])  # norm 0.5
    np.testing.assert_allclose(flat.clip_to_l2(v, 1.0), v)


def test_clip_to_l2_scales_to_exactly_clip():
    v = jnp.array([3.0, 4.0])  # norm 5
    out = flat.clip_to_l2(v, 1.0)
    np.testing.assert_allclose(jnp.linalg.norm(out), 1.0, rtol=1e-6)
    np.testing.assert_allclose(out, v / 5.0, rtol=1e-6)


def test_global_norm_clip_torch_semantics():
    v = jnp.array([3.0, 4.0])
    out = flat.global_norm_clip(v, 2.0)
    # torch multiplies by max_norm / (norm + 1e-6)
    np.testing.assert_allclose(out, v * (2.0 / (5.0 + 1e-6)), rtol=1e-6)
    np.testing.assert_allclose(flat.global_norm_clip(v, 10.0), v)


def test_flatten_roundtrip():
    params = {"a": jnp.ones((2, 3)), "b": {"w": jnp.arange(4.0)}}
    vec, unravel = flat.flatten_params(params)
    assert vec.shape == (10,)
    back = unravel(vec)
    np.testing.assert_allclose(back["a"], params["a"])
    np.testing.assert_allclose(back["b"]["w"], params["b"]["w"])


def test_dp_noise_stats():
    key = jax.random.PRNGKey(0)
    noise = flat.dp_noise(key, (20000,), noise_multiplier=2.0, scale=3.0)
    assert abs(float(jnp.std(noise)) - 6.0) < 0.2
    assert abs(float(jnp.mean(noise))) < 0.2


def test_masked_topk_jits():
    f = jax.jit(lambda v: flat.masked_topk(v, 3))
    v = jnp.arange(10.0) - 5.0
    out = f(v)
    assert int((out != 0).sum()) == 3

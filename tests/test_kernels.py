"""Per-kernel validation: Pallas (interpret mode) vs pure-jnp oracle.

Sweeps shapes and dtypes per the deliverable spec; hypothesis drives
randomized shapes/content for the filter kernels.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis",
                    reason="property tests need hypothesis (pip install -e .[dev])")
from hypothesis import given, settings, strategies as st

from repro.kernels import ops, ref

SHAPES = [(1, 8, 8), (2, 24, 32), (1, 33, 17), (3, 48, 64)]
RADII = [1, 3, 7]
DTYPES = [jnp.float32, jnp.bfloat16]


def _img(shape, dtype, seed=0):
    r = np.random.default_rng(seed)
    return jnp.asarray(r.random(shape + (3,), np.float32)).astype(dtype)


def _map(shape, dtype, seed=1):
    r = np.random.default_rng(seed)
    return jnp.asarray(r.random(shape, np.float32)).astype(dtype)


def _tol(dtype):
    return 1e-5 if dtype == jnp.float32 else 2e-2


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("radius", RADII)
@pytest.mark.parametrize("dtype", DTYPES)
def test_dark_channel_matches_oracle(shape, radius, dtype):
    img = _img(shape, dtype)
    got = ops.dark_channel(img, radius, mode="interpret")
    want = ref.dark_channel(img, radius)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               atol=_tol(dtype))


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("radius", RADII)
@pytest.mark.parametrize("dtype", DTYPES)
def test_box_filter_matches_oracle(shape, radius, dtype):
    x = _map(shape, dtype)
    got = ops.box_filter_2d(x, radius, mode="interpret")
    want = ref.box_filter_2d(x, radius)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               atol=_tol(dtype) * 4)


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("radius", [1, 5])
def test_min_filter_matches_oracle(shape, radius):
    x = _map(shape, jnp.float32)
    got = ops.min_filter_2d(x, radius, mode="interpret")
    np.testing.assert_allclose(got, ref.min_filter_2d(x, radius), atol=1e-6)


@pytest.mark.parametrize("shape", SHAPES)
def test_atmolight_matches_oracle(shape):
    img, t = _img(shape, jnp.float32), _map(shape, jnp.float32)
    got = ops.atmospheric_light(img, t, k=1, mode="interpret")
    want = ref.atmospheric_light(img, t, k=1)
    np.testing.assert_allclose(got, want, atol=1e-6)


def test_atmolight_tiled_grid():
    """Multi-tile sequential-grid fold must equal the global argmin."""
    from repro.kernels.atmolight import atmolight_pallas
    img, t = _img((2, 32, 16), jnp.float32), _map((2, 32, 16), jnp.float32)
    got = atmolight_pallas(img, t, tile_h=8, interpret=True)
    want = ref.atmospheric_light(img, t, k=1)
    np.testing.assert_allclose(got, want, atol=1e-6)


def _tie_map(case):
    """A quantized transmission map with a tie pattern at its minimum."""
    lead = {"batch_ties": (4,), "lanes": (3, 2)}.get(case, (2,))
    shape = lead + (6, 16)
    t = np.round(np.random.default_rng(7).uniform(0.25, 1.0, shape) * 8) / 8
    if case == "constant":
        t[:] = 0.5
    elif case == "row_plateau":                   # rows 2-4, and row 3 col 0
        t[..., 2:5, 5:] = 0.125
        t[..., 3, 0] = 0.125
    elif case == "last_pixel":
        t[..., -1, -1] = 0.0
    elif case == "batch_ties":                    # one min value, several
        t[0, 1, 9] = t[1, 4, 2] = t[2, 0, 15] = 0.125     # places per frame
        t[2, 5, 0] = t[3, 3, 3] = t[3, 3, 4] = 0.125
    elif case == "lanes":
        t[..., 1:, 7] = 0.125
        t[1, 0, 0, 15] = t[2, 1, 5, 0] = 0.125
    return jnp.asarray(t, jnp.float32)


@pytest.mark.parametrize("case", ["constant", "row_plateau", "last_pixel",
                                  "batch_ties", "lanes"])
def test_atmolight_k1_ties_match_topk(case):
    """k=1 is a first-index argmin reduction: it picks exactly the pixel
    ``lax.top_k(-t, 1)`` picks, ties included, over any leading dims."""
    t = _tie_map(case)
    img = _img(t.shape, jnp.float32, seed=3)
    flat_t = t.reshape(*t.shape[:-2], -1)
    _, idx = jax.lax.top_k(-flat_t, 1)
    want = jnp.take_along_axis(img.reshape(*t.shape[:-2], -1, 3),
                               idx[..., None], axis=-2).mean(axis=-2)
    np.testing.assert_array_equal(np.asarray(ref.first_min_index(t)),
                                  np.asarray(idx[..., 0]))
    np.testing.assert_array_equal(np.asarray(ref.atmospheric_light(img, t)),
                                  np.asarray(want))


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("gamma", [1.0, 2.2])
def test_recover_matches_oracle(shape, dtype, gamma):
    img = _img(shape, dtype)
    t = _map(shape, dtype)
    A = jnp.asarray(np.random.default_rng(2).random((shape[0], 3)),
                    dtype)
    got = ops.recover(img, t, A, gamma=gamma, mode="interpret")
    want = ref.recover(img, t, A)
    if gamma != 1.0:
        want = want ** gamma
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               atol=_tol(dtype) * 4)


@pytest.mark.parametrize("radius", [2, 6])
def test_guided_filter_matches_oracle(radius):
    g = _map((2, 32, 24), jnp.float32)
    p = _map((2, 32, 24), jnp.float32, seed=3)
    got = ops.guided_filter(g, p, radius, 1e-3, mode="interpret")
    want = ref.guided_filter(g, p, radius, 1e-3)
    np.testing.assert_allclose(got, want, atol=1e-4)


@pytest.mark.parametrize("radius", [1, 3, 7])
def test_masked_kernels_match_spatial_reference(radius):
    """The halo-path masked kernels (row-validity masks) must match the
    reduce_window reference used by the sharded pipeline."""
    from repro.core import spatial
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.random((2, 24, 32), np.float32))
    valid = jnp.asarray(
        np.concatenate([np.zeros(5), np.ones(14), np.zeros(5)]).astype(bool))
    got = ops.masked_min_filter_2d(x, valid, radius, mode="interpret")
    want = spatial.masked_min_filter_2d(x, valid, radius)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-6)
    got = ops.masked_box_filter_2d(x, valid, radius, mode="interpret")
    want = spatial.masked_box_filter_2d(x, valid, radius)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)


def test_masked_kernels_all_valid_equal_unmasked():
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.random((1, 16, 16), np.float32))
    valid = jnp.ones((16,), bool)
    np.testing.assert_allclose(
        np.asarray(ops.masked_min_filter_2d(x, valid, 3, mode="interpret")),
        np.asarray(ref.min_filter_2d(x, 3)), atol=1e-6)
    np.testing.assert_allclose(
        np.asarray(ops.masked_box_filter_2d(x, valid, 3, mode="interpret")),
        np.asarray(ref.box_filter_2d(x, 3)), atol=1e-5)


# --- hypothesis sweeps -----------------------------------------------------

@settings(max_examples=20, deadline=None)
@given(h=st.integers(4, 40), w=st.integers(4, 40), r=st.integers(0, 8),
       seed=st.integers(0, 2 ** 16))
def test_min_filter_property(h, w, r, seed):
    x = _map((1, h, w), jnp.float32, seed)
    got = np.asarray(ops.min_filter_2d(x, r, mode="interpret"))[0]
    xn = np.asarray(x)[0]
    # Oracle-by-definition: brute-force clipped window min.
    i, j = np.random.default_rng(seed).integers(0, (h, w))
    want = xn[max(0, i - r):i + r + 1, max(0, j - r):j + r + 1].min()
    np.testing.assert_allclose(got[i, j], want, atol=1e-6)


@settings(max_examples=20, deadline=None)
@given(h=st.integers(4, 32), w=st.integers(4, 32), r=st.integers(0, 6),
       seed=st.integers(0, 2 ** 16))
def test_box_filter_property(h, w, r, seed):
    x = _map((1, h, w), jnp.float32, seed)
    got = np.asarray(ops.box_filter_2d(x, r, mode="interpret"))[0]
    xn = np.asarray(x)[0]
    i, j = np.random.default_rng(seed).integers(0, (h, w))
    win = xn[max(0, i - r):i + r + 1, max(0, j - r):j + r + 1]
    np.testing.assert_allclose(got[i, j], win.mean(), rtol=1e-5, atol=1e-5)


# --- top-k atmospheric-light selector (kernels.atmolight.topk_select) ------

def _distinct_tmap(h, w, seed):
    """A transmission map with pairwise-distinct values (a scaled
    permutation of arange), so top-k selection is order-unambiguous."""
    perm = np.random.default_rng(seed).permutation(h * w)
    return jnp.asarray(perm.reshape(1, h, w).astype(np.float32) / (h * w))


@settings(max_examples=20, deadline=None)
@given(h=st.integers(4, 24), w=st.integers(4, 24), k=st.integers(1, 16),
       seed=st.integers(0, 2 ** 16))
def test_topk_selector_permutation_invariant(h, w, k, seed):
    """Permuting the pixels (jointly in t and I) must not change the
    mean-of-top-k A: the selected (t, rgb) multiset is permutation-
    invariant when the t values are distinct."""
    img = _img((1, h, w), jnp.float32, seed)
    t = _distinct_tmap(h, w, seed)
    perm = np.random.default_rng(seed + 1).permutation(h * w)
    img_p = jnp.asarray(np.asarray(img).reshape(1, -1, 3)[:, perm]
                        ).reshape(1, h, w, 3)
    t_p = jnp.asarray(np.asarray(t).reshape(1, -1)[:, perm]).reshape(1, h, w)
    a = ops.atmospheric_light(img, t, k=k, mode="interpret")
    a_p = ops.atmospheric_light(img_p, t_p, k=k, mode="interpret")
    np.testing.assert_allclose(np.asarray(a), np.asarray(a_p), atol=1e-7)


@settings(max_examples=20, deadline=None)
@given(h=st.integers(4, 24), w=st.integers(4, 24),
       seed=st.integers(0, 2 ** 16))
def test_topk_selector_k1_reduces_to_argmin(h, w, seed):
    """k=1 must be the Eq. 6 argmin-t pixel — identical to both the
    dedicated argmin kernel and the direct gather, including ties (ties
    resolve to the lowest flat index, so a tie-heavy quantized map is used
    half the time)."""
    img = _img((1, h, w), jnp.float32, seed)
    t = _map((1, h, w), jnp.float32, seed + 1)
    if seed % 2:
        t = jnp.round(t * 4) / 4                      # force ties
    from repro.kernels.atmolight import atmolight_topk_pallas
    got = atmolight_topk_pallas(img, t, k=1, interpret=True)
    want = ops.atmospheric_light(img, t, k=1, mode="interpret")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-7)
    j = int(np.argmin(np.asarray(t).reshape(-1)))
    np.testing.assert_allclose(np.asarray(got)[0],
                               np.asarray(img).reshape(-1, 3)[j], atol=1e-7)


@settings(max_examples=20, deadline=None)
@given(h=st.integers(4, 16), w=st.integers(4, 16),
       seed=st.integers(0, 2 ** 16))
def test_topk_selector_full_k_is_global_mean(h, w, seed):
    """k = H*W selects every pixel: A must equal the full image mean."""
    img = _img((1, h, w), jnp.float32, seed)
    t = _map((1, h, w), jnp.float32, seed + 1)
    got = ops.atmospheric_light(img, t, k=h * w, mode="interpret")
    want = np.asarray(img).reshape(-1, 3).mean(axis=0)
    np.testing.assert_allclose(np.asarray(got)[0], want, atol=1e-5)


@settings(max_examples=20, deadline=None)
@given(h=st.integers(4, 24), w=st.integers(4, 24), k=st.integers(2, 8),
       tile=st.integers(1, 6), seed=st.integers(0, 2 ** 16))
def test_topk_selector_tiled_fold_matches_oracle(h, w, k, tile, seed):
    """The k-row running selection folded across row tiles (the atmolight
    grid carry) must equal the whole-frame lax.top_k oracle, ties included."""
    img = _img((1, h, w), jnp.float32, seed)
    t = jnp.round(_map((1, h, w), jnp.float32, seed + 1) * 8) / 8
    from repro.kernels.atmolight import atmolight_topk_pallas
    got = atmolight_topk_pallas(img, t, k=k, tile_h=tile, interpret=True)
    want = ref.atmospheric_light(img, t, k=k)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-6)


# --- 2-D (H x W) masked box mean (kernels.boxfilter._masked_box_mean) ------

@settings(max_examples=20, deadline=None)
@given(h=st.integers(4, 24), w=st.integers(4, 24), r=st.integers(0, 6),
       seed=st.integers(0, 2 ** 16))
def test_masked_box_mean_all_valid_equals_unmasked(h, w, r, seed):
    """A mask of all-valid rows AND columns must reproduce the unmasked
    kernel exactly — the column-count fix must not perturb the interior."""
    x = _map((1, h, w), jnp.float32, seed)
    got = ops.masked_box_filter_2d(x, jnp.ones((h,), bool), r,
                                   jnp.ones((w,), bool), mode="interpret")
    want = ref.box_filter_2d(x, r)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)
    got = ops.masked_min_filter_2d(x, jnp.ones((h,), bool), r,
                                   jnp.ones((w,), bool), mode="interpret")
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(ref.min_filter_2d(x, r)), atol=1e-6)


@settings(max_examples=20, deadline=None)
@given(h=st.integers(6, 24), w=st.integers(6, 24), r=st.integers(1, 5),
       lo_h=st.integers(0, 3), hi_h=st.integers(0, 3),
       lo_w=st.integers(0, 3), hi_w=st.integers(0, 3),
       seed=st.integers(0, 2 ** 16))
def test_masked_box_mean_2d_matches_spatial_reference(h, w, r, lo_h, hi_h,
                                                      lo_w, hi_w, seed):
    """Random separable edge masks (the halo-exchange shapes): the in-VMEM
    separable row x column divisor must match the reduce_window reference
    that sums the full 2-D mask."""
    x = _map((1, h, w), jnp.float32, seed)
    valid_h = (jnp.arange(h) >= lo_h) & (jnp.arange(h) < h - hi_h)
    valid_w = (jnp.arange(w) >= lo_w) & (jnp.arange(w) < w - hi_w)
    from repro.core import spatial
    got = ops.masked_box_filter_2d(x, valid_h, r, valid_w, mode="interpret")
    want = spatial.masked_box_filter_2d(x, valid_h, r, valid_w)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)

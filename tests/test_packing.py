"""Single-transfer output fetch (engine/packing.py): bit-exact pytree
round trip through the packed uint8 buffer for every dtype the kernels
emit, and layout-cache correctness across shape changes."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from pinot_tpu.engine.packing import make_packed_kernel


def test_packed_round_trip_mixed_tree():
    def fn(a, b):
        return {
            "f32": a * 2.0,
            "pair": (a.sum(), b + 1),
            "i8": b.astype(jnp.int8),
            "u16": b.astype(jnp.uint16),
            "bool": a > 0.5,
            "scalar": jnp.float32(3.25),
            "empty": jnp.zeros((0, 4), jnp.float32),
        }

    a = np.linspace(0, 1, 37, dtype=np.float32)
    b = np.arange(37, dtype=np.int32)
    packed = make_packed_kernel(fn, "pinot_test_pack")
    got = packed(jnp.asarray(a), jnp.asarray(b))
    want = jax.tree_util.tree_map(np.asarray, fn(jnp.asarray(a), jnp.asarray(b)))

    assert set(got) == set(want)
    for k in want:
        g, w = got[k], want[k]
        if isinstance(w, tuple):
            for gg, ww in zip(g, w):
                np.testing.assert_array_equal(np.asarray(gg), np.asarray(ww))
        else:
            assert np.asarray(g).dtype == np.asarray(w).dtype, k
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


def test_packed_layout_cache_shape_change():
    def fn(x):
        return {"sum": x.sum(axis=0), "sq": x * x}

    packed = make_packed_kernel(fn, "pinot_test_pack")
    for n in (8, 16, 8):  # revisit the first shape: cache hit must hold
        x = np.arange(n * 3, dtype=np.float32).reshape(n, 3)
        got = packed(jnp.asarray(x))
        np.testing.assert_allclose(got["sum"], x.sum(axis=0), rtol=1e-6)
        np.testing.assert_allclose(got["sq"], x * x, rtol=1e-6)
        assert isinstance(got["sum"], np.ndarray)


def test_packed_f64_under_x64():
    if not jax.config.read("jax_enable_x64"):
        pytest.skip("x64 disabled")

    def fn(x):
        return {"d": x.astype(jnp.float64) / 3.0}

    x = np.arange(11, dtype=np.float64)
    got = make_packed_kernel(fn, "pinot_test_pack")(jnp.asarray(x))
    assert got["d"].dtype == np.float64
    np.testing.assert_allclose(got["d"], x / 3.0)


def test_npgroup_matches_ufunc_at():
    """utils/npgroup sorted-reduceat primitives are drop-in equivalents
    of np.maximum.at (property check over random shapes)."""
    import numpy as np

    from pinot_tpu.utils.npgroup import group_max_rows, scatter_max_2d

    rng = np.random.default_rng(7)
    for _ in range(5):
        R, G, M = int(rng.integers(1, 400)), int(rng.integers(1, 12)), 16
        inverse = rng.integers(0, G, R)
        vals2d = rng.integers(0, 60, (R, M)).astype(np.uint8)
        want = np.zeros((G, M), np.uint8)
        np.maximum.at(want, inverse, vals2d)
        # group_max_rows only defined for groups with >=1 row: compare
        # on non-empty groups
        got = group_max_rows(inverse, G, vals2d)
        present = np.unique(inverse)
        np.testing.assert_array_equal(got[present], want[present])

        cols = rng.integers(0, M, R)
        vals = rng.integers(0, 60, R).astype(np.uint8)
        want2 = np.zeros((G, M), np.uint8)
        np.maximum.at(want2, (inverse, cols), vals)
        np.testing.assert_array_equal(scatter_max_2d(inverse, G, cols, vals, M), want2)
    # empty input
    np.testing.assert_array_equal(
        scatter_max_2d(np.zeros(0, np.int64), 3, np.zeros(0, np.int64), np.zeros(0, np.uint8), 4),
        np.zeros((3, 4), np.uint8),
    )

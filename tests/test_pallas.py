"""Pallas fused-kernel tests (interpret mode on CPU; real-chip lowering
is validated when TPU hardware is attached)."""
import numpy as np
import pytest

import jax.numpy as jnp

from pinot_tpu.engine.pallas_kernels import fused_filtered_groupby_sums


def test_fused_groupby_matches_numpy():
    rng = np.random.default_rng(0)
    n = 5000
    card_f, card_g, card_v = 7, 6, 50
    filter_fwd = rng.integers(0, card_f, n).astype(np.int32)
    match = np.zeros(card_f, dtype=bool)
    match[[1, 3, 4]] = True
    valid = np.ones(n, dtype=bool)
    valid[-13:] = False
    keys = rng.integers(0, card_g, n).astype(np.int32)
    v_fwd = rng.integers(0, card_v, n).astype(np.int32)
    v_dict = np.round(rng.uniform(0, 100, card_v), 2)

    docs, count, (sums,) = fused_filtered_groupby_sums(
        jnp.asarray(filter_fwd),
        jnp.asarray(match),
        jnp.asarray(valid),
        jnp.asarray(keys),
        [jnp.asarray(v_fwd)],
        [jnp.asarray(v_dict)],
        capacity=card_g,
        interpret=True,
    )

    mask = match[filter_fwd] & valid
    np.testing.assert_allclose(float(docs), mask.sum())
    want_count = np.bincount(keys[mask], minlength=card_g)
    np.testing.assert_allclose(np.asarray(count), want_count, rtol=1e-6)
    vals = v_dict[v_fwd]
    want_sums = np.bincount(keys[mask], weights=vals[mask], minlength=card_g)
    np.testing.assert_allclose(np.asarray(sums), want_sums, rtol=1e-5)


def test_fused_groupby_multi_value_columns():
    rng = np.random.default_rng(3)
    n = 1000
    keys = rng.integers(0, 4, n).astype(np.int32)
    filter_fwd = np.zeros(n, dtype=np.int32)
    match = np.ones(1, dtype=bool)
    valid = np.ones(n, dtype=bool)
    fwds = [rng.integers(0, 10, n).astype(np.int32) for _ in range(3)]
    dicts = [np.arange(10, dtype=np.float64) * (i + 1) for i in range(3)]

    docs, count, sums = fused_filtered_groupby_sums(
        jnp.asarray(filter_fwd),
        jnp.asarray(match),
        jnp.asarray(valid),
        jnp.asarray(keys),
        [jnp.asarray(f) for f in fwds],
        [jnp.asarray(d) for d in dicts],
        capacity=4,
        interpret=True,
    )
    assert float(docs) == n
    np.testing.assert_allclose(np.asarray(count), np.bincount(keys, minlength=4))
    for i in range(3):
        want = np.bincount(keys, weights=dicts[i][fwds[i]], minlength=4)
        np.testing.assert_allclose(np.asarray(sums[i]), want, rtol=1e-5)


def test_value_state_counts_pallas_matches_xla():
    """The Pallas occupancy histogram (VMEM-resident accumulator)
    matches the XLA factored contraction bit-for-bit, for K both a
    multiple of 128 and not, under direct and vmapped use (the kernel
    runs inside the vmapped per-segment program)."""
    import numpy as np
    import jax
    import jax.numpy as jnp

    from pinot_tpu.engine.kernel import (
        _value_state_counts,
        _value_state_counts_pallas,
    )

    rng = np.random.default_rng(12)
    for K in (16384, 300):
        n = 6000
        idx_np = rng.integers(0, K, size=n).astype(np.int32)
        idx_np[rng.random(n) < 0.05] = K  # dropped sentinel entries
        idx = jnp.asarray(idx_np)
        a = np.asarray(_value_state_counts(idx, K))
        b = np.asarray(_value_state_counts_pallas(idx, K, interpret=True))
        assert a.shape == b.shape == (K,)
        assert np.array_equal(a, b), K
        # ground truth
        want = np.bincount(idx_np[idx_np < K], minlength=K)
        assert np.array_equal(a, want.astype(a.dtype))

    K = 1024
    batch = jnp.asarray(rng.integers(0, K, size=(3, 4096)).astype(np.int32))
    va = np.asarray(jax.vmap(lambda i: _value_state_counts(i, K))(batch))
    vb = np.asarray(jax.vmap(lambda i: _value_state_counts_pallas(i, K, interpret=True))(batch))
    assert np.array_equal(va, vb)

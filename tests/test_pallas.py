"""Pallas kernel tests (interpret mode on CPU; the chip's lowering is
held by tests/test_tpu_compile.py and tests/test_tpu_platform.py)."""


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

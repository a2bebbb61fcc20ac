"""Compile the group-by contraction kernel for a v5e that is described,
not attached: the TPU's compiler is installed here, and what it refuses
(a slice off the tiling, more VMEM than a kernel may use) it would
refuse on the chip.  Nothing runs, so nothing here is a time or a
result; ``tests/test_engine.py::test_radix_groupby_forced`` holds the
answers.  One file, one worker: the topology is described inside a
fixture, never at import."""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


# (groups, segments, rows a segment, float columns): the closed cell's Q3
# and Q4, the gate's bound with two sums and with ten, a ragged row count
SHAPES = {
    "q3_k2000": (2000, 16, 1 << 23, 1),
    "q4_k2000": (2000, 16, 1 << 23, 2),
    "k513": (513, 4, 1 << 23, 1),
    "bound_two_sums": (1 << 16, 4, 1 << 23, 2),
    "bound_ten_sums": (1 << 16, 2, 1 << 22, 10),
    "ragged_rows": (2000, 4, 3 * (1 << 20) + 5, 1),
}


@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_radix_contraction_compiles_for_v5e(one_chip, monkeypatch, shape):
    from pinot_tpu.engine import kernel as kernel_mod

    K, S, n, m = SHAPES[shape]
    assert K <= kernel_mod.RADIX_GROUP_CAP
    # the kernel asks the backend whether to run in the Pallas
    # interpreter; this compile is for the described chip
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

    def table(idx, *ws):
        single = lambda i, *xs: kernel_mod._segment_add_radix(i, [jnp.where(i < K, x, 0) for x in xs], K)
        return jnp.sum(jax.vmap(single)(idx, *ws), axis=0)

    idx = jax.ShapeDtypeStruct((S, n), jnp.int32, sharding=one_chip)
    w = jax.ShapeDtypeStruct((S, n), jnp.float32, sharding=one_chip)
    with jax.enable_x64(False):  # the chip's process runs float32 and int32; the suite's runs x64
        compiled = jax.jit(table).lower(idx, *([w] * m)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    # the one-hots stay in VMEM: what the program keeps in HBM beside its
    # arguments is the masked weight columns and no [rows, K1 + 128] operand
    assert compiled.memory_analysis().temp_size_in_bytes <= (m + 1) * S * n * 4 + (64 << 20)

"""The main path's Pallas kernels compile for a TPU v5e at deployment width.

No chip is needed: the TPU compiler compiles for a described (not attached)
``v5e:2x2`` topology, with ``interpret=False``, at p = 2048 (pooled
ResNet-50 features) and n = 16384 samples.  This is what refuses a kernel
whose blocks outgrow VMEM or whose ops Mosaic cannot lower — neither shows in
the interpret-mode tests.  A compile that passes is not a run on the chip.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops

P, N_SAMPLES = 2048, 16384


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here: nothing to check
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip can be written to the persistent cache
    # but not read back without one
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, *shapes, sharding, dtypes=None):
    args = [
        jax.ShapeDtypeStruct(s, (dtypes or {}).get(i, jnp.float32), sharding=sharding)
        for i, s in enumerate(shapes)
    ]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("n_features", [1024, 4096])
@pytest.mark.parametrize("fused", [False, True], ids=["materialized", "fused"])
def test_gram_stream_compiles_for_v5e(one_chip, fused, n_features):
    plan = ops.gram_tile_plan(n_features, P, fused=fused)
    assert plan["vmem_bytes"] <= ops.GRAM_VMEM_BUDGET
    if fused:
        _compile(
            lambda x, e: ops.rff_gram_stream_fused(
                x, e, n_features=n_features, seed=3, interpret=False
            ),
            (P, N_SAMPLES), (N_SAMPLES,), sharding=one_chip,
        )
    else:
        _compile(
            lambda x, o, e: ops.rff_gram_stream(x, o, e, interpret=False),
            (P, N_SAMPLES), (n_features, P), (N_SAMPLES,), sharding=one_chip,
        )


@pytest.mark.parametrize("fused", [False, True], ids=["materialized", "fused"])
def test_rff_featurize_compiles_for_v5e(one_chip, fused):
    if fused:
        _compile(
            lambda x: ops.rff_fused(x, n_features=1024, seed=3, interpret=False),
            (P, 4096), sharding=one_chip,
        )
    else:
        _compile(
            lambda x, o: ops.rff(x, o, interpret=False),
            (P, 4096), (1024, P), sharding=one_chip,
        )


# (K, D, E): a 1024-client fleet over 32 edges merging its 2N = 2048 moments,
# and its flattened W_RF (2N * m = 65536) plus the mass column — the width an
# untiled D axis runs out of VMEM at
@pytest.mark.parametrize("k,d,e", [(1024, P, 32), (1024, 2 * 1024 * 32 + 1, 32)])
def test_segment_reduce_compiles_for_v5e(one_chip, k, d, e):
    _compile(
        lambda v, s, w: ops.segment_reduce(v, s, w, n_segments=e, interpret=False),
        (k, d), (k,), (k,), sharding=one_chip, dtypes={1: jnp.int32},
    )


def test_fake_quant_compiles_for_v5e(one_chip):
    _compile(
        lambda x, u: ops.fake_quant(x, u, bits=8, interpret=False),
        (2 * 1024, 32), (2 * 1024, 32), sharding=one_chip,
    )

"""Ahead-of-time compiles of the served Pallas kernels for a TPU v5e.

Nothing runs: each kernel is lowered and compiled against a described
v5e chip (``jax.experimental.topologies``), which raises what the chip's
Mosaic compiler would refuse — block shapes off the (8, 128) tiling,
memory spaces, too much VMEM/SMEM.  Shapes are a deployment's: N = 100k
rows, a B = 64 query batch, C = M·R = 256 candidates per query, at the
SIFT (128) and GIST (960) widths.  The compile also shows the table is read
in the layout the index stores: no temporary buffer grows with N·d.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.dedup import dedupdist, dedupdist_int8
from repro.kernels.l2dist import l2dist_dma, l2dist_rowgather
from repro.quant.kernels import int8dist_rowgather

N, B, C = 100_000, 64, 256
LANES = 128
# per-call temporaries stay O(B·C); a relayout of the table would be O(N·d)
TEMP_LIMIT = 16 << 20


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, shapes, sharding):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding)
            for s, dt in shapes]
    return jax.jit(fn).lower(*args).compile()


def _check(compiled):
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < TEMP_LIMIT


F32_KERNELS = {
    "rowgather": l2dist_rowgather,
    "dma": l2dist_dma,
    "dedup_gather": dedupdist,
}


@pytest.mark.parametrize("d", [128, 960])
@pytest.mark.parametrize("name", sorted(F32_KERNELS))
def test_f32_kernel_compiles_for_v5e(one_chip, name, d):
    k = -(-d // LANES)
    compiled = _compile(
        F32_KERNELS[name],
        [((N, k, LANES), jnp.float32), ((B, C), jnp.int32),
         ((B, d), jnp.float32)], one_chip)
    _check(compiled)


def _int8_shapes(d, b, c):
    return [((N, d // LANES, LANES), jnp.int8), ((N, 1), jnp.float32),
            ((b, c), jnp.int32), ((b, d), jnp.float32)]


@pytest.mark.parametrize("name,fn", [("rowgather_int8", int8dist_rowgather),
                                     ("dedup_gather_int8", dedupdist_int8)])
def test_int8_kernel_compiles_for_v5e(one_chip, name, fn):
    _check(_compile(fn, _int8_shapes(128, B, C), one_chip))


# the largest batch the serving benchmarks send (B = 256) against the
# widest candidate grid a degree-64 graph expands (C = M·R = 8 · 64): the
# ids and distances held in SMEM grow with B·C, so the kernels split such
# a batch into launches that fit (kernels.platform.MAX_PAIRS)
B_MAX, C_MAX = 256, 512


@pytest.mark.parametrize("name", sorted(F32_KERNELS) + ["dedup_gather_int8",
                                                        "rowgather_int8"])
def test_kernel_compiles_for_v5e_at_largest_batch(one_chip, name):
    d = 128
    if name.endswith("int8"):
        fn = {"rowgather_int8": int8dist_rowgather,
              "dedup_gather_int8": dedupdist_int8}[name]
        shapes = _int8_shapes(d, B_MAX, C_MAX)
    else:
        fn = F32_KERNELS[name]
        shapes = [((N, d // LANES, LANES), jnp.float32),
                  ((B_MAX, C_MAX), jnp.int32), ((B_MAX, d), jnp.float32)]
    _check(_compile(fn, shapes, one_chip))

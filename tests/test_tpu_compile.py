"""Ahead-of-time compiles of the Pallas kernels for a described TPU v5e.

The TPU compiler ships with jaxlib's TPU plug-in, so every kernel is
compiled here for a v5e chip that is described, not attached: the
compiler refuses what the chip would refuse (block tiling, unsupported
casts and reshapes, VMEM over-use), at the widths a deployment runs.
Interpret-mode parity lives in the other test files; these tests only
prove that the compiled program exists and carries the Mosaic kernel,
with x64 off and on.

The topology is described inside a module-scoped fixture (never at
import time), so test workers that are not given this file never load
the TPU library. The persistent compilation cache is off around these
compiles: an executable built for a described chip cannot be read back
without one.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.bucket_min import bucket_min_pallas
from repro.kernels.bucket_update import MAX_UPDATE_CAP, bucket_update_pallas
from repro.kernels.butterfly_combine import butterfly_combine_pallas
from repro.kernels.wedge_count import wedge_histogram_pallas
from repro.kernels.wedge_fused import MAX_TILE_CAP, match_tiles_pallas


@pytest.fixture(scope="module")
def chip():
    from jax.experimental.compilation_cache import compilation_cache as cc

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        from jax.experimental import topologies

        try:
            topo = topologies.get_topology_desc(
                platform="tpu", topology_name="v5e:2x2"
            )
        except Exception as e:  # noqa: BLE001 — any backend refusal
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield SingleDeviceSharding(topo.devices[0])
    finally:
        jax.config.update("jax_enable_compilation_cache", was_on)
        cc.reset_cache()


@pytest.fixture(params=[False, True], ids=["x32", "x64"])
def one_chip(chip, request):
    """The described chip, with x64 off (the library default) and on
    (how the benchmarks and ``chip_smoke.py`` run): under x64 a bare
    Python float inside a kernel becomes float64, which Mosaic refuses."""
    with jax.enable_x64(request.param):
        yield chip


def _arg(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_kernel(compiled, name):
    text = compiled.as_text()
    assert 'custom_call_target="tpu_custom_call"' in text
    assert f"/{name}/pallas_call" in text, name


def test_wedge_count_compiles(one_chip):
    n = 1 << 22
    c = wedge_histogram_pallas.lower(
        _arg((n,), jnp.int32, one_chip), _arg((n,), jnp.int32, one_chip),
        num_buckets=1 << 17, interpret=False,
    ).compile()
    _assert_kernel(c, "wedge_count")


def test_butterfly_combine_compiles(one_chip):
    n = 1 << 24
    c = butterfly_combine_pallas.lower(
        *(_arg((n,), jnp.int32, one_chip) for _ in range(3)),
        interpret=False,
    ).compile()
    _assert_kernel(c, "butterfly_combine")


def test_bucket_min_compiles(one_chip):
    n = 1 << 21
    c = bucket_min_pallas.lower(
        _arg((n,), jnp.int32, one_chip), _arg((n,), jnp.bool_, one_chip),
        interpret=False,
    ).compile()
    _assert_kernel(c, "bucket_min")


def test_bucket_update_compiles(one_chip):
    n = 1 << 20
    k = MAX_UPDATE_CAP
    c = bucket_update_pallas.lower(
        _arg((n,), jnp.int32, one_chip), _arg((n,), jnp.bool_, one_chip),
        _arg((k,), jnp.int32, one_chip), _arg((k,), jnp.int32, one_chip),
        interpret=False,
    ).compile()
    _assert_kernel(c, "bucket_update")


def test_wedge_fused_compiles(one_chip):
    tiles, t = 8, MAX_TILE_CAP
    c = match_tiles_pallas.lower(
        _arg((tiles, t), jnp.int32, one_chip),
        _arg((tiles, t), jnp.int32, one_chip),
        interpret=False,
    ).compile()
    _assert_kernel(c, "wedge_fused")

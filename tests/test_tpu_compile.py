"""Ahead-of-time compiles of the Pallas kernels for a TPU v5e chip.

Every other kernel test runs the kernels in interpret mode, which checks
their logic but not their tiling; only the TPU compiler checks that.
These tests compile for a *described* v5e chip (none is attached) at
real widths and assert that the Mosaic kernel is in the executable
(``tpu_custom_call``), so a layout the chip refuses fails here. The
topology is described inside a fixture — never at import — because only
one process at a time may load the TPU library.
"""
import contextlib
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.analysis.roofline import peaks
from repro.kernels import ops


@pytest.fixture(scope="module")
def one_chip(tmp_path_factory):
    # the TPU library's own log files go to the test's temporary directory
    os.environ.setdefault("TPU_LOG_DIR", str(tmp_path_factory.mktemp("tpu-logs")))
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no TPU compiler installed, or it is busy
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@contextlib.contextmanager
def _no_persistent_cache():
    """A compile for a described chip cannot be read back from the
    persistent cache without that chip; keep it out of the cache."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)
        cc.reset_cache()


def _compile(fn, *args):
    with _no_persistent_cache():
        return jax.jit(fn).lower(*args).compile()


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_fits(compiled, chip):
    m = compiled.memory_analysis()
    total = m.argument_size_in_bytes + m.output_size_in_bytes + m.temp_size_in_bytes
    (device,) = chip.device_set
    assert total < peaks(device.device_kind)["hbm_bytes"], total


def _assert_kernel_fits(compiled, chip):
    assert "tpu_custom_call" in compiled.as_text()
    _assert_fits(compiled, chip)


@pytest.mark.parametrize("kernel", ["flat", "sorted"])
def test_packed_segmin_kernel_compiles_for_v5e(one_chip, kernel):
    """The production pair at E = 2^20 edges, 2^17 segments."""
    e, s = 1 << 20, 1 << 17
    fn = ops.segment_min_flat if kernel == "flat" else ops.segment_min_sorted
    compiled = _compile(
        lambda k, g: fn(k, g, num_segments=s, interpret=False),
        _spec(one_chip, (e,), jnp.uint32),
        _spec(one_chip, (e,), jnp.int32),
    )
    _assert_kernel_fits(compiled, one_chip)


@pytest.mark.parametrize("kernel", ["flat", "sorted"])
def test_small_packed_segmin_compiles_for_v5e(one_chip, kernel):
    """Fewer edges and segments than one block: the wrappers pad them to
    128 multiples only, and the kernel takes each as one smaller block."""
    e, s = 300, 200
    fn = ops.segment_min_flat if kernel == "flat" else ops.segment_min_sorted
    compiled = _compile(
        lambda k, g: fn(k, g, num_segments=s, interpret=False),
        _spec(one_chip, (e,), jnp.uint32),
        _spec(one_chip, (e,), jnp.int32),
    )
    _assert_kernel_fits(compiled, one_chip)


def test_bucketed_segmin_kernel_compiles_for_v5e(one_chip):
    compiled = _compile(
        lambda k, r: ops.segment_min_bucketed(k, r, interpret=False),
        _spec(one_chip, (1024, 1024), jnp.uint32),
        _spec(one_chip, (1024, 1024), jnp.int32),
    )
    _assert_kernel_fits(compiled, one_chip)


def test_dense_multilinear_kernel_compiles_for_v5e(one_chip):
    n = 4096
    compiled = _compile(
        lambda p, a: ops.multilinear_dense(p, a, interpret=False),
        _spec(one_chip, (n,), jnp.int32),
        _spec(one_chip, (n, n), jnp.float32),
    )
    _assert_kernel_fits(compiled, one_chip)


def _mosaic_segmin(kernel):
    fn = ops.segment_min_flat if kernel == "flat" else ops.segment_min_sorted

    def segmin(keys, segs, num_segments):
        return fn(keys, segs, num_segments=num_segments, interpret=False)

    return segmin


def test_packed_flat_driver_compiles_for_v5e(one_chip):
    """The whole packed AS driver with the flat kernel in its hook loop,
    at a stream union's shape for n = 2^20 (2^21 + 1024 directed slots)."""
    from repro.core.msf import _msf_jit
    from repro.graphs.structures import Graph

    n, e = 1 << 20, (1 << 21) + 1024
    g = Graph(
        src=_spec(one_chip, (e,), jnp.int32),
        dst=_spec(one_chip, (e,), jnp.int32),
        w=_spec(one_chip, (e,), jnp.float32),
        eid=_spec(one_chip, (e,), jnp.int32),
        valid=_spec(one_chip, (e,), jnp.bool_),
        n=n,
    )
    with _no_persistent_cache():
        compiled = _msf_jit.lower(
            g, pack=True, segmin=_mosaic_segmin("flat")
        ).compile()
    _assert_kernel_fits(compiled, one_chip)


def test_unpacked_flat_driver_compiles_for_v5e(one_chip):
    """The unpacked AS driver above pack32's 2^24 slots: the slot-rank
    sorts before the loop and the rank-keyed hook inside it."""
    from repro.core.msf import _msf_jit
    from repro.graphs.structures import Graph

    n, e = 1 << 20, (1 << 24) + 1024
    g = Graph(
        src=_spec(one_chip, (e,), jnp.int32),
        dst=_spec(one_chip, (e,), jnp.int32),
        w=_spec(one_chip, (e,), jnp.float32),
        eid=_spec(one_chip, (e,), jnp.int32),
        valid=_spec(one_chip, (e,), jnp.bool_),
        n=n,
    )
    with _no_persistent_cache():
        compiled = _msf_jit.lower(g, pack=False).compile()
    text = compiled.as_text()
    assert "/rank/sort" in text and "/hook/segmin/scatter-min" in text
    _assert_fits(compiled, one_chip)


def test_fused_coarsen_level_compiles_for_v5e(one_chip):
    """One fused coarsening level with the device dedupe on the sorted
    kernel, at 2^22 undirected edge slots over 2^19 vertices."""
    from repro.coarsen.engine import fused_level

    n, e = 1 << 19, 1 << 22
    ints = _spec(one_chip, (e,), jnp.int32)
    with _no_persistent_cache():
        compiled = fused_level.lower(
            ints, ints,
            _spec(one_chip, (e,), jnp.float32),
            ints,
            _spec(one_chip, (e,), jnp.bool_),
            _spec(one_chip, (n,), jnp.int32),
            n=n, eid_capacity=e, pack=True,
            segmin_dedupe=_mosaic_segmin("sorted"),
        ).compile()
    _assert_kernel_fits(compiled, one_chip)

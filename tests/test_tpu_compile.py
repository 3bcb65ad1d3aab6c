"""The jax plane's kernels compile for a TPU v5e at real size.

Each kernel the plane runs on the device is compiled here for one chip of
a described (not attached) ``v5e`` topology, at 2^20 rows — the bucket a
1M-row table pads to — with float64/int64 operands under x64, exactly as
the plane calls it.  What the chip's compiler would refuse fails here, at
no chip time; the compile-time bound catches a kernel that regresses to
minutes (a device sort at this size takes XLA:TPU minutes to compile).

The topology is described inside a fixture only: loading the TPU compiler
while a module is imported would make pytest-xdist workers collect
different tests.
"""

import time

import pytest

ROWS = 1 << 20
KERNELS = ("filter_mul", "filter_mask", "project_sum", "join_probe")
MAX_COMPILE_S = 60.0


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def v5e_specs(topo):
    """``kernel_specs`` at ``ROWS`` with avals on one described chip,
    compiled with the persistent cache off: an entry compiled for a chip
    that is not attached cannot be read back."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    from repro.engine.plane.jax_plane import JaxPlane

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    one_chip = SingleDeviceSharding(topo.devices[0])
    yield {
        name: (fn, [jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)
                    for a in avals])
        for name, fn, avals in JaxPlane().kernel_specs(ROWS)
    }
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.mark.parametrize("kernel", KERNELS)
def test_kernel_compiles_for_v5e(v5e_specs, kernel):
    import jax

    fn, args = v5e_specs[kernel]
    with jax.enable_x64(True):
        t0 = time.perf_counter()
        compiled = jax.jit(fn).lower(*args).compile()
        seconds = time.perf_counter() - t0
        out = jax.tree_util.tree_leaves(jax.eval_shape(fn, *args))
    assert "tpu_custom_call" not in compiled.as_text()  # plain XLA, no Mosaic
    assert seconds < MAX_COMPILE_S, f"{kernel} took {seconds:.1f}s to compile"
    assert all(o.shape == (ROWS,) for o in out)

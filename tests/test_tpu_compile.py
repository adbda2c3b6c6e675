"""The main path's kernel, compiled HERE for the chip that is not attached.

The TPU's compiler is installed in the sandbox and compiles for a described
v5e (on-chip-measurement guide §2): what Mosaic refuses, it refuses here at
no chip time. One file, topology described inside a fixture, compile cache
off around it (such a compile can be written to the cache but not read
back without a chip)."""
import re

import pytest


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture()
def no_compile_cache():
    import jax
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def test_projection_kernel_compiles_for_v5e_under_its_names(
        one_chip, no_compile_cache):
    """`analyst-groupby`'s program at its real shape — a 5M-row day segment,
    100,000 groups padded to 2^17, count + longSum + floatMax — through
    Mosaic, and named so that the profiler's `device_ops` read
    `jit_seg_agg_pallas/proj_group_reduce.<n>`."""
    import jax
    import jax.numpy as jnp

    from druid_tpu.data.generator import ColumnSpec, DataGenerator
    from druid_tpu.engine import contracts, pallas_agg
    from druid_tpu.engine.kernels import make_kernel
    from druid_tpu.query.aggregators import (FloatMaxAggregator,
                                             LongSumAggregator)
    from druid_tpu.utils.intervals import Interval
    seg = DataGenerator(
        (ColumnSpec("d", "string", cardinality=5),
         ColumnSpec("m", "long", low=0, high=100),
         ColumnSpec("f", "float")), seed=1).segment(
             1000, Interval.of("2026-01-01", "2026-01-02"), datasource="x")
    kernels = [make_kernel(LongSumAggregator("s", "m"), seg),
               make_kernel(FloatMaxAggregator("x", "f"), seg)]
    rows = -(-5_000_000 // 2048) * 2048

    def prog(key, m, f):
        counts, states, _raw = pallas_agg.grouped_reduce(
            {"m": m, "f": f}, key, None, kernels, 1 << 17, 512)
        return counts, states

    fn = jax.jit(contracts.named_program(
        prog, contracts.program_name("seg_agg", "pallas")))
    was = pallas_agg._FORCE_INTERPRET
    pallas_agg.force_interpret(False)
    try:
        lowered = fn.lower(
            jax.ShapeDtypeStruct((rows,), jnp.int32, sharding=one_chip),
            jax.ShapeDtypeStruct((rows,), jnp.int32, sharding=one_chip),
            jax.ShapeDtypeStruct((rows,), jnp.float32, sharding=one_chip))
        assert lowered.as_text().split("module @", 1)[1].split()[0] \
            == "jit_seg_agg_pallas"
        hlo = lowered.compile().as_text()
    finally:
        pallas_agg.force_interpret(was)
    calls = [line for line in hlo.splitlines() if "tpu_custom_call" in line]
    assert calls, "no Mosaic kernel in the compiled program"
    # the operation's name is the kernel's, as xplane.short_op will cut it
    names = {re.match(r"\s*(?:ROOT\s+)?%?([\w.\-]+) = ", line).group(1)
             for line in calls}
    assert all(n.split(".")[0] == contracts.PALLAS_KERNEL_NAMES[0]
               for n in names), names

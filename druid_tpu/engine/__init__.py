"""Query engines: queries compile to jit-ted mask + segmented-reduce programs.

x64 is enabled globally: OLAP long sums must not overflow int32, and
timestamps are int64 host-side. Device kernels still use int32/float32 where
hot (time offsets, dictionary ids, float metrics); int64 work on TPU lowers
to emulated 32-bit pairs only where a query actually asks for longs.
"""
import os

import jax

jax.config.update("jax_enable_x64", True)

# persistent XLA compilation cache: repeated-shape queries skip the cold
# compile across PROCESSES (the reference's warm JVM + code cache have no
# cold start; this is our equivalent). Placement belongs to the deployment:
# JAX itself reads JAX_COMPILATION_CACHE_DIR, and when that is set no
# directory is set here. Otherwise ONE fixed, git-ignored directory inside
# the checkout — a directory that moves with the machine never hits.
COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")
if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
    jax.config.update("jax_compilation_cache_dir", COMPILE_CACHE_DIR)

from druid_tpu.engine.executor import QueryExecutor  # noqa: E402


def release_device_caches(clear_pool: bool = False) -> dict:
    """Drop every process-wide cache that pins device memory across
    queries: the sharded stack cache (whole segment sets held HBM-resident
    — and the segment OBJECTS each entry pins), the jitted-program LRUs
    (closures capture kernel aux arrays), and, with `clear_pool=True`, the
    device segment pool's entries. The ops surface for reclaiming HBM
    without a restart; the leak witness's session check uses it so that
    deliberately-pinned cache state is not mistaken for a leak. Returns
    per-cache drop counts."""
    from druid_tpu.engine import batching, grouping
    from druid_tpu.parallel import distributed

    with grouping._JIT_CACHE_LOCK:
        grouping_n = len(grouping._JIT_CACHE)
        grouping._JIT_CACHE.clear()
    with batching._JIT_CACHE_LOCK:
        batching_n = len(batching._JIT_CACHE)
        batching._JIT_CACHE.clear()
    out = {
        "stack_entries": distributed.clear_stack_cache(),
        "sharded_programs": distributed.clear_fn_cache(),
        "grouping_programs": grouping_n,
        "batching_programs": batching_n,
    }
    if clear_pool:
        from druid_tpu.data.devicepool import device_pool
        pool = device_pool()
        out["pool_resident_bytes"] = pool.snapshot().resident_bytes
        pool.clear()
    return out


__all__ = ["QueryExecutor", "release_device_caches"]

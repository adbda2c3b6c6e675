"""A cell's run rehearsed on the CPU at a tiny size.

    JAX_PLATFORMS=cpu python benchmark/tests/rehearse.py --workload <name>
        [--trace 0|1] [--seconds 2] [--seed N] [--root <a copy of benchmark/>]

Not the benchmark: `run.py` refuses without a TPU, and so it should. This
stubs that one check, puts Pallas in interpret mode, and cuts the
configuration to 4 segments × 4,096 rows IN MEMORY (no file is changed); a
closed loop's plan is made long enough for a CPU's pace. Everything else —
data, deployment, warm-up, the child load generator, the reference check, the
reduction, the last line — is `run.py`'s own code. The mesh configuration
needs `XLA_FLAGS=--xla_force_host_platform_device_count=4`.
"""
import argparse
import os
import sys


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=2.0)
    ap.add_argument("--seed", type=int, default=3_000_000_007)
    ap.add_argument("--root", default=os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    args = ap.parse_args(argv)
    sys.path.insert(0, os.path.dirname(os.path.abspath(args.root)))
    import benchmark.run as run
    assert os.path.samefile(run.HERE, args.root), (run.HERE, args.root)
    import jax

    from druid_tpu.engine import pallas_agg
    pallas_agg.force_interpret(True)
    load = run.load_json

    def tiny(kind, name):
        spec = load(kind, name)
        if kind == "configs":
            spec = dict(spec, segments=4, rows_per_segment=4096)
        if kind == "workloads" and spec.get("loop", {}).get("kind") == "closed":
            spec = dict(spec, loop=dict(spec["loop"], plan_qps=60))
        return spec

    run.load_json = tiny
    device = {"platform": jax.devices()[0].platform,
              "kind": jax.devices()[0].device_kind,
              "count": len(jax.devices())}
    return run.run_cell(argparse.Namespace(
        workload=args.workload, seed=args.seed, seconds=args.seconds,
        trace=args.trace), stub_device=device)


if __name__ == "__main__":
    sys.exit(main())

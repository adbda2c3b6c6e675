"""Record a SHORT device trace on the chip, for `tests/data/`.

    chiprun -- python benchmark/tests/record_trace.py

A builder's tool, not part of the benchmark's command: a few jitted calls
with known sleeps between them under `jax.profiler`, the `.xplane.pb` copied
to `chiprun_out/trace_sample/`, and a summary of its planes and lines
printed, so that `harness/xplane.py` can be written against what the chip
really records and checked against it ever after.
"""
import glob
import json
import os
import shutil
import sys
import time


def main() -> int:
    import jax
    import jax.numpy as jnp
    if jax.devices()[0].platform != "tpu":
        print("record_trace: no TPU", file=sys.stderr)
        return 2
    out = os.path.join("chiprun_out", "trace_sample")
    shutil.rmtree(out, ignore_errors=True)
    os.makedirs(out)

    @jax.jit
    def work(x):
        return (x @ x).sum()

    x = jnp.ones((2048, 2048), jnp.float32)
    work(x).block_until_ready()
    tmp = os.path.join(out, "raw")
    jax.profiler.start_trace(tmp)
    wall0 = time.time()
    with jax.profiler.TraceAnnotation("bench_anchor"):
        time.sleep(0.002)
    marks = []
    for _ in range(3):
        t = time.time()
        work(x).block_until_ready()
        marks.append([t, time.time()])
        time.sleep(0.05)
    wall1 = time.time()
    jax.profiler.stop_trace()
    path = glob.glob(os.path.join(tmp, "plugins", "profile", "*", "*.xplane.pb"))[0]
    shutil.copy(path, os.path.join(out, "sample.xplane.pb"))
    shutil.rmtree(tmp)
    with open(os.path.join(out, "sample.json"), "w") as f:
        json.dump({"wall0": wall0, "wall1": wall1, "calls": marks,
                   "device_kind": jax.devices()[0].device_kind}, f)
    data = jax.profiler.ProfileData.from_file(os.path.join(out, "sample.xplane.pb"))
    for plane in data.planes:
        print("PLANE", plane.name)
        for line in plane.lines:
            events = list(line.events)
            print("  LINE", repr(line.name), len(events))
            for e in events[:6]:
                print("     ", repr(e.name), e.start_ns, e.duration_ns)
    print("size", os.path.getsize(os.path.join(out, "sample.xplane.pb")))
    print(json.dumps({"wall0": wall0, "wall1": wall1, "calls": marks}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""A data worker: makes and persists some day segments of one configuration.

    JAX_PLATFORMS=cpu python benchmark/harness/makedata.py --config C --seed N
        --segments 0,8,16 --seg-dir D --raw-dir R

Started by `deploy.ensure_data`, several at once, with `JAX_PLATFORMS=cpu` in
its environment: the writer imports jax, and the chip belongs to the parent.
Prints the bytes written as its last line.
"""
import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--segments", required=True)
    ap.add_argument("--seg-dir", required=True)
    ap.add_argument("--raw-dir", required=True)
    args = ap.parse_args(argv)
    if os.environ.get("JAX_PLATFORMS") != "cpu":
        print("makedata: JAX_PLATFORMS must be cpu", file=sys.stderr)
        return 2
    from benchmark.harness import deploy
    with open(args.config) as f:
        config = json.load(f)
    indices = [int(i) for i in args.segments.split(",") if i != ""]
    print(deploy.make_segments(config, args.seed, indices, args.seg_dir,
                               args.raw_dir), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""Read the numbers that ``correct`` compares, on the chip, over many
seeds in one process: the readings that each limit in a configuration's
``limits`` is set from.

    python3 benchmark/readings.py --workload sift1m-ivf_flat.batch10k \\
        --seeds 11,12,13 --seconds 3 [--control]

Without ``--control`` these are sound runs of the program (the lower
reading of each number); with it the family's control, one precision step
below the configuration's, takes the search's place (the upper reading).
The benchmark's own runs never run the control. One JSON line a seed.
"""
import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--rate", type=float, default=None,
                    help="a served cell's offered rate in place of its own")
    args = ap.parse_args(argv)

    sys.path[:0] = [HERE, ROOT]
    from raft_tpu.utils import use_compile_cache

    use_compile_cache(ROOT)
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print("the readings are taken only on the chip", file=sys.stderr)
        return 2
    import harness

    over = None
    if args.rate is not None:
        over = {"traffic": {"rate_per_s": args.rate}}
    for seed in (int(s) for s in args.seeds.split(",")):
        res = harness.run_cell(args.workload, seed, args.seconds, False,
                               devices[:1], control=args.control,
                               overrides=over)
        print(json.dumps({"seed": seed, "control": args.control,
                          "correct": res["correct"],
                          "attempted": res["attempted"],
                          "checks": res["checks"],
                          "metrics": res["metrics"]}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

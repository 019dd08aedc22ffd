"""The benchmark's command: one run of one cell on the chip it is started
on.

    python bench/run.py --workload internlm2-1.8b.decode_long --seed 7 \\
        --seconds 30 --trace 0

Prints the result as one JSON line, last on standard output, and each
number compared for ``correct`` beside its limit, last on standard error.
Exits non-zero, printing no result, where JAX finds no TPU.
"""
import time

T0 = time.perf_counter()     # set-up is timed from here

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parent))


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    import harness
    c = harness.Cell.by_name(args.workload)
    result, _ = harness.run(c, args.seed, args.seconds, bool(args.trace), T0)
    print(json.dumps(result), flush=True)
    for name, v in result["check"].items():
        print(f"check {name}: {v['value']} limit {v['limit']}",
              file=sys.stderr, flush=True)


if __name__ == "__main__":
    main()

"""Readings that set a cell's correctness limit, on the chip: one run of
the cell as the benchmark makes it (its window, its sizes) and, in the
same process once the program's state is freed, the control's reading.

    python bench/control.py --workload internlm2-1.8b.decode_long \\
        --seconds 10 --seed 101 [--fault half_pages]

Without ``--fault`` it prints the program's widest logit gap over the
served tokens of the sampled requests and the control's: the float8
reference in the program's place, read at the same positions (the gap of
the token it puts first). With ``--fault`` it plants that fault
(``bench/faults.py``) in the program and prints the program's reading
alone. One seed per process: each run holds the chip's memory as a
benchmark run does. The benchmark's own runs never run the control.
"""
import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parent))


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--fault", default=None)
    args = ap.parse_args(argv)
    import numpy as np

    import cell
    import faults
    import harness
    import reference

    c = harness.Cell.by_name(args.workload)
    if args.fault:
        cell.use_program()
        faults.install(args.fault)
    res, rec = harness.run(c, args.seed, args.seconds, False, T0)
    line = {"workload": c.name, "seed": args.seed, "fault": args.fault,
            "correct": res["correct"],
            "program": res["check"]["max_logit_gap"]["value"],
            "served_tokens": sum(len(v) for v in rec["served"].values()),
            "metrics": {k: v["value"] for k, v in res["metrics"].items()}}
    if not args.fault:
        t = time.perf_counter()
        m, arch = rec["dims"], cell.arch(rec["arch"])
        params = arch.make(m, args.seed, c.config["torch_dtype"])
        line["control"] = max(
            float(reference.served_gaps(
                arch, params, m, np.asarray(rec["prompts"][rid], np.int32),
                np.asarray(served, np.int32), control=True).max())
            for rid, served in rec["served"].items())
        line["control_s"] = time.perf_counter() - t
    print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main()

"""The on-chip benchmark of the RkMIPS serving path.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

Runs one cell of ``BENCHMARK.json`` on the chip this process finds and
prints the result as the last line of standard output (one JSON object);
the compared numbers, each beside its limit, are the last lines of
standard error. With no TPU it exits non-zero and prints no result.

Also, not used by the benchmark's own runs:
  --control      the reference one precision step below the
                 configuration's, in the program's place (the control
                 that ``correct`` must refuse);
  --sweep R      after one set-up, windows at doubling rates from R and
                 one bisection; prints the knee and 0.8 x it;
  --rehearse     on the CPU at the configuration's ``rehearse`` sizes;
  --readings N   N runs in one process, on the seeds seed + i x 1000003,
                 one result line each (the readings a limit is set from);
  --keep-trace F with --trace 1, the traced window's .xplane.pb copied to
                 F (how tests/data's chip trace was recorded).
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))

from rkbench import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--sweep", type=float, default=None)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--readings", type=int, default=1)
    ap.add_argument("--keep-trace", default=None)
    args = ap.parse_args(argv)
    t_start = T_START
    for i in range(args.readings):
        seed = args.seed + i * 1_000_003
        try:
            line = harness.run(args.workload, seed, args.seconds,
                               bool(args.trace), t_start=t_start,
                               rehearse=args.rehearse, control=args.control,
                               sweep=args.sweep,
                               keep_trace=args.keep_trace)
        except harness.NoChip as e:
            print(f"no chip: {e}", file=sys.stderr, flush=True)
            return 3
        if args.readings > 1:
            line = {"seed": seed, **line}
        print(json.dumps(line), flush=True)
        t_start = time.perf_counter()
    return 0


if __name__ == "__main__":
    sys.exit(main())

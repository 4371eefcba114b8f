"""Record the chip trace and op-scope map that test_rkbench_spans.py reads.

    python3 benchmarks/chip/tests/record_spans_trace.py OUT_DIR [--seed N]

One traced second of the forward cell on the chip (``harness.run`` with
``keep_trace``, as ``run.py --seconds 1 --trace 1 --keep-trace`` does),
then, from the same process, the program's ``serving.op_scopes()``.
Writes ``OUT_DIR/forward_spans_trace.xplane.pb.gz`` and
``OUT_DIR/forward_spans_scopes.json`` ([module, instruction, scope]
triples, ambiguous instructions with scope null), and prints the run's
result line.
"""

import argparse
import gzip
import json
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

from rkbench import harness  # noqa: E402

WORKLOAD = "amazon-cds.forward-k50"


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("out_dir")
    ap.add_argument("--seed", type=int, default=4400000009)
    args = ap.parse_args()
    out = pathlib.Path(args.out_dir)
    out.mkdir(parents=True, exist_ok=True)
    raw = out / "forward_spans_trace.xplane.pb"
    line = harness.run(WORKLOAD, args.seed, 1.0, True,
                       t_start=time.perf_counter(), keep_trace=str(raw))
    from repro.engine import serving
    scopes = [[m, i, s] for (m, i), s in sorted(serving.op_scopes().items())]
    (out / "forward_spans_scopes.json").write_text(json.dumps(scopes))
    (out / (raw.name + ".gz")).write_bytes(gzip.compress(raw.read_bytes(),
                                                         9))
    raw.unlink()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

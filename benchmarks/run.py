"""Benchmark driver: one harness per paper table/figure + roofline.

Prints ``name,us_per_call,derived`` CSV. Scale with --scale {smoke,bench}.
``--json PATH`` additionally writes the rows plus environment metadata as
JSON — the format of the checked-in perf baselines (BENCH_rkmips.json):

    PYTHONPATH=src python -m benchmarks.run --scale smoke \
        --only rkmips,artifact,serving,kernels --host-devices 8 \
        --json BENCH_rkmips.json

``--host-devices N`` forces an N-device host (CPU) backend before jax
initializes, which turns on the mesh-sharded build columns of the rkmips
suite (engine/build.py) on a single machine.
"""

from __future__ import annotations

import argparse
import datetime
import json
import os
import sys
import time


def _row_to_json(row: str) -> dict:
    name, us, derived = row.split(",", 2)
    return {"name": name, "us_per_call": float(us), "derived": derived}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", choices=("smoke", "bench"), default="bench")
    ap.add_argument("--only", default=None,
                    help="comma-separated subset: rkmips,artifact,serving,"
                         "load,adversarial,kmips,params,kernels,roofline")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="also write rows + run metadata as JSON")
    ap.add_argument("--host-devices", type=int, default=None, metavar="N",
                    help="force N host (CPU) devices before jax "
                         "initializes — enables the mesh-sharded build "
                         "columns of the rkmips suite on one machine")
    args = ap.parse_args()

    if args.host_devices:
        # must land before the first jax import (pulled in transitively by
        # the benchmarks import below)
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count"
              f"={args.host_devices}").strip()

    from benchmarks import (bench_adversarial, bench_artifact,
                            bench_kernels, bench_kmips, bench_load,
                            bench_params, bench_rkmips, bench_roofline,
                            bench_serving)
    from repro import compile_cache
    compile_cache.enable()

    small = args.scale == "smoke"
    suites = {
        "rkmips": lambda: bench_rkmips.run(
            n=2048 if small else 8192, m=4096 if small else 16384,
            nq=8 if small else 16,
            ks=(1, 10, 50) if small else (1, 5, 10, 20, 30, 40, 50)),
        "artifact": lambda: bench_artifact.run(
            n=2048 if small else 8192, m=4096 if small else 16384,
            nq=8 if small else 16, cap=128 if small else 256),
        "serving": lambda: bench_serving.run(
            n=2048 if small else 8192, m=4096 if small else 16384,
            nq=8 if small else 16, cap=128 if small else 256,
            steady_rounds=48 if small else 128),
        "load": lambda: bench_load.run(
            n=2048 if small else 8192, m=4096 if small else 16384,
            nq=8 if small else 16, cap=128 if small else 256,
            duration=3.0 if small else 10.0,
            rates=(16.0, 48.0) if small else (32.0, 96.0)),
        "adversarial": lambda: bench_adversarial.run(
            n=2048 if small else 8192, m=4096 if small else 16384,
            nq=8 if small else 16,
            rate=24.0 if small else 48.0,
            duration=3.0 if small else 10.0),
        "kmips": lambda: bench_kmips.run(
            n=4096 if small else 16384, m=4096 if small else 16384,
            nq=8 if small else 32,
            ks=(1, 10, 50) if small else (1, 5, 10, 20, 30, 40, 50)),
        "params": lambda: bench_params.run(
            n=2048 if small else 4096, m=4096 if small else 8192,
            nq=4 if small else 8),
        "kernels": lambda: bench_kernels.run(n=8192 if small else 65536),
        "roofline": bench_roofline.run,
    }
    if args.only:
        keep = set(args.only.split(","))
        suites = {k: v for k, v in suites.items() if k in keep}

    all_rows: list[str] = []
    print("name,us_per_call,derived")
    for name, fn in suites.items():
        t0 = time.time()
        try:
            for row in fn():
                print(row, flush=True)
                all_rows.append(row)
        except Exception as e:  # noqa: BLE001
            print(f"{name}/ERROR,0,{type(e).__name__}:{e}", flush=True)
            raise
        print(f"# suite {name} done in {time.time()-t0:.1f}s",
              file=sys.stderr)

    if args.json:
        import jax
        doc = {
            "meta": {
                "date": datetime.date.today().isoformat(),
                "scale": args.scale,
                "suites": sorted(suites),
                "jax": jax.__version__,
                "backend": jax.default_backend(),
                "device_count": jax.device_count(),
            },
            "rows": [_row_to_json(r) for r in all_rows],
        }
        with open(args.json, "w") as f:
            json.dump(doc, f, indent=1)
            f.write("\n")
        print(f"# wrote {args.json} ({len(all_rows)} rows)",
              file=sys.stderr)


if __name__ == "__main__":
    main()

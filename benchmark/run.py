#!/usr/bin/env python3
"""Run one cell of BENCHMARK.json once.

    python3 benchmark/run.py --workload <name> --seed <n> \
        --seconds <s> --trace <0|1>

A new process every time: it starts one server through the program's
normal entry point (`python -m pilosa_tpu.cli server --platform tpu`;
with `--trace 1` through `harness/launcher.py`, the same `cmd_server`
under `jax.profiler`), loads the configuration's data over HTTP on the
first run in a checkout and re-opens the kept directory afterwards,
warms up, drives the cell's closed-loop traffic for `--seconds`, stops
the server, compares the answers with the numpy reference, and prints
one JSON object as the last line of stdout: `correct`, `attempted`,
`failed`, `metrics`, `device` (and `breakdown` when traced). With
`--trace 0` the metrics are the cell's end-to-end metrics, with
`--trace 1` its per-layer metrics.

It exits non-zero and prints no result when the server does not come up
on a TPU with the chips the cell asks for. `--platform cpu` with
`--shards`/`--grid-rows` is the rehearsal off the chip (the tests use
it); the result line then names the CPU.
"""

import argparse
import json
import os
import sys
import time

T_PROC = time.monotonic()
HERE = os.path.dirname(os.path.abspath(__file__))
CHECKOUT = os.path.dirname(HERE)
sys.path.insert(0, HERE)

from harness.cell import log, run_cell  # noqa: E402
from harness.server import BenchFailure  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--platform", default="tpu", choices=("tpu", "cpu"))
    ap.add_argument("--shards", type=int, default=0)
    ap.add_argument("--grid-rows", type=int, default=0)
    args = ap.parse_args(argv)
    sizes = {k: v for k, v in (("shards", args.shards),
                               ("grid_rows", args.grid_rows)) if v}
    try:
        result = run_cell(CHECKOUT, args.workload, args.seed, args.seconds,
                          bool(args.trace), T_PROC, args.platform, sizes)
    except (BenchFailure, FileNotFoundError, KeyError) as e:
        log(f"FAILED: {e}")
        return 1
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

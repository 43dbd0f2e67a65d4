#!/usr/bin/env python3
"""The control of a cell, at the cell's own size: answers altered
between the server and the load generator; `correct` must come out
false.

    python3 benchmark/control.py --workload <name> --seconds <s> \
        --seeds <n> [<n> ...]

Each seed is one whole run of the cell (`harness.cell.run_cell`) with
`harness.tamper.TamperedServer` in the server's place. One line per
seed: CONTROL <cell> seed <n> {"correct": false, ...}. Exit code 0 when
every seed came out not correct, 1 when one passed. The benchmark's own
runs never run this; `PERF.md` §2 quotes its readings.
"""

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
CHECKOUT = os.path.dirname(HERE)
sys.path.insert(0, HERE)

from harness import cell, tamper  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--platform", default="tpu", choices=("tpu", "cpu"))
    ap.add_argument("--shards", type=int, default=0)
    ap.add_argument("--grid-rows", type=int, default=0)
    args = ap.parse_args(argv)
    sizes = {k: v for k, v in (("shards", args.shards),
                               ("grid_rows", args.grid_rows)) if v}
    cell.Server = tamper.TamperedServer
    passed = 0
    for seed in args.seeds:
        res = cell.run_cell(CHECKOUT, args.workload, seed, args.seconds,
                            False, time.monotonic(), args.platform, sizes)
        passed += res["correct"]
        print(f"CONTROL {args.workload} seed {seed}", json.dumps(
            {"correct": res["correct"], "attempted": res["attempted"],
             "failed": res["failed"],
             "altered": tamper.TamperedServer.altered,
             "device": res["device"]["kind"]}), flush=True)
    return 1 if passed else 0


if __name__ == "__main__":
    sys.exit(main())

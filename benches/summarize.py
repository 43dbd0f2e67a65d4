"""Summarize benches/*_r0N_<backend>.jsonl records into one markdown
table.

Usage: python benches/summarize.py [round] [backend]
       (defaults: round 4, backend cpu)

Skips partial records (a leg killed mid-run leaves {"partial": true});
flags invalid device-time rows (above-roofline measurements are stored
with "invalid": true rather than suppressed)."""

import glob
import json
import os
import sys


def load(path):
    recs = []
    with open(path) as fh:
        for line in fh:
            line = line.strip()
            if not line:
                continue
            try:
                r = json.loads(line)
            except ValueError:
                continue  # severed line from a mid-print TERM
            if isinstance(r, dict) and r.get("metric"):
                recs.append(r)
    return recs


def fmt(v):
    if isinstance(v, float):
        if v >= 1000:
            return f"{v:,.0f}"
        if v >= 1:
            return f"{v:.2f}"
        return f"{v:.4g}"
    return str(v)


def main():
    rnd = sys.argv[1] if len(sys.argv) > 1 else "4"
    backend = sys.argv[2] if len(sys.argv) > 2 else "cpu"
    base = os.path.dirname(os.path.abspath(__file__))
    paths = sorted(glob.glob(
        os.path.join(base, f"*_r0{rnd}_{backend}.jsonl")))
    if not paths:
        print(f"(no *_r0{rnd}_{backend}.jsonl records yet)")
        return
    print(f"| Leg | Metric | Value | Unit | vs_baseline | Notes |")
    print(f"|---|---|---|---|---|---|")
    for p in paths:
        leg = os.path.basename(p).replace(f"_r0{rnd}_{backend}.jsonl", "")
        for r in load(p):
            if r.get("partial"):
                continue
            notes = []
            if r.get("invalid"):
                notes.append("INVALID (above roofline)")
            if r.get("error"):
                notes.append(str(r["error"])[:60])
            for k in ("roofline_frac", "gbps_min", "gbps_max", "p50_query_s",
                      "backend", "platform", "device_kind"):
                if k in r:
                    notes.append(f"{k}={fmt(r[k])}")
            print(f"| {leg} | {r['metric']} | {fmt(r.get('value', ''))} | "
                  f"{r.get('unit', '')} | "
                  f"{fmt(r.get('vs_baseline', ''))} | "
                  f"{'; '.join(notes)} |")


if __name__ == "__main__":
    try:
        main()
    except BrokenPipeError:  # `| head` closed the pipe; not an error
        pass

#!/usr/bin/env python3
"""One-command diagnostic bundles.

Makes a live server's observability surface portable: one timestamped
JSON bundle per incident, diffable against another capture, checked
for internal consistency.

    # Snapshot every debug surface of a live server into one bundle
    python tools/doctor.py snapshot --base http://localhost:10101 \
        -o bundle.json

    # Structural diff of two bundles (volatile keys normalized away);
    # exit 0 iff no differences remain
    python tools/doctor.py diff before.json after.json

    # Judge a bundle: internal-consistency checks; exit 1 on any FAIL
    python tools/doctor.py baseline bundle.json

Stdlib only (urllib) — the tool must run on a box that has nothing
but the checkout."""

from __future__ import annotations

import argparse
import json
import sys
import time
import urllib.request
from typing import Any, Dict, List, Optional, Tuple

# Every surface a bundle captures: bundle key -> path. A surface that
# errors is RECORDED with its error, never dropped — a 500 on
# /debug/memory is itself a diagnostic fact.
SURFACES = [
    ("memory", "/debug/memory"),
    ("queries", "/debug/queries"),
    ("hotspots", "/debug/hotspots"),
    ("timeline", "/debug/timeline"),
    ("health", "/internal/health"),
    ("cluster_health", "/cluster/health"),
    # Identity/config group: schema + versions + cluster topology.
    ("status", "/status"),
    ("info", "/info"),
    ("version", "/version"),
    ("schema", "/schema"),
]

# Keys whose values are wall-clock / monotonically-churning state:
# normalized away before diffing so two captures of the same healthy
# server diff down to the differences that matter.
VOLATILE_KEYS = frozenset({
    "t", "ts", "time", "now", "uptimeS", "ageS", "lastSampleAt",
    "lastRunAt", "capturedAt", "samples", "samplesTaken",
    "traceEvents", "_received",
})


def fetch_json(base: str, path: str, timeout: float = 10.0) -> Any:
    req = urllib.request.Request(base.rstrip("/") + path,
                                 headers={"Accept": "application/json"})
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return json.loads(r.read().decode())


def snapshot_bundle(base: str, timeout: float = 10.0) -> Dict[str, Any]:
    bundle: Dict[str, Any] = {
        "doctorBundle": 1,
        "base": base,
        "capturedAt": time.time(),
        "surfaces": {},
    }
    for key, path in SURFACES:
        try:
            bundle["surfaces"][key] = {"path": path,
                                       "doc": fetch_json(base, path,
                                                         timeout)}
        except Exception as e:
            bundle["surfaces"][key] = {
                "path": path,
                "error": f"{type(e).__name__}: {e}"}
    return bundle


# ------------------------------------------------------------------ diff

def _normalize(obj: Any) -> Any:
    if isinstance(obj, dict):
        return {k: _normalize(v) for k, v in obj.items()
                if k not in VOLATILE_KEYS}
    if isinstance(obj, list):
        return [_normalize(v) for v in obj]
    return obj


def diff_docs(a: Any, b: Any, path: str = "",
              out: Optional[List[str]] = None) -> List[str]:
    """Structural diff: one line per added/removed/changed leaf."""
    if out is None:
        out = []
    if isinstance(a, dict) and isinstance(b, dict):
        for k in sorted(set(a) | set(b)):
            p = f"{path}.{k}" if path else str(k)
            if k not in a:
                out.append(f"+ {p} = {json.dumps(b[k], default=str)[:120]}")
            elif k not in b:
                out.append(f"- {p} = {json.dumps(a[k], default=str)[:120]}")
            else:
                diff_docs(a[k], b[k], p, out)
    elif isinstance(a, list) and isinstance(b, list):
        if len(a) != len(b):
            out.append(f"~ {path}: list len {len(a)} -> {len(b)}")
        for i, (x, y) in enumerate(zip(a, b)):
            diff_docs(x, y, f"{path}[{i}]", out)
    elif a != b:
        out.append(f"~ {path}: {json.dumps(a, default=str)[:60]} -> "
                   f"{json.dumps(b, default=str)[:60]}")
    return out


# -------------------------------------------------------------- verdicts

def _get(doc: Any, *keys: str, default: Any = None) -> Any:
    for k in keys:
        if not isinstance(doc, dict) or k not in doc:
            return default
        doc = doc[k]
    return doc


def judge_bundle(bundle: Dict[str, Any]) -> List[Tuple[str, str, str]]:
    """Internal-consistency verdicts: (check, PASS|FAIL|SKIP, detail)
    triples. Any FAIL makes the CLI exit nonzero."""
    verdicts: List[Tuple[str, str, str]] = []
    surfaces = bundle.get("surfaces", {})

    def add(check: str, ok: Optional[bool], detail: str,
            skip: bool = False) -> None:
        verdicts.append((check,
                         "SKIP" if skip else ("PASS" if ok else "FAIL"),
                         detail))

    for key, _path in SURFACES:
        s = surfaces.get(key) or {}
        add(f"surface:{key}", "error" not in s,
            s.get("error", "captured"))

    mem = _get(surfaces, "memory", "doc")
    if isinstance(mem, dict):
        cats = mem.get("categories") or {}
        total = sum(int(c.get("bytes", 0)) for c in cats.values())
        add("memory.totals-consistent",
            total == int(mem.get("totalBytes", -1)),
            f"sum(categories)={total} totalBytes="
            f"{mem.get('totalBytes')}")
    else:
        add("memory.totals-consistent", None, "no memory surface",
            skip=True)

    health = _get(surfaces, "health", "doc")
    if isinstance(health, dict):
        add("health.healthy", bool(health.get("healthy")),
            f"state={health.get('state')}")

    return verdicts


# ------------------------------------------------------------------ CLI

def cmd_snapshot(args) -> int:
    bundle = snapshot_bundle(args.base, timeout=args.timeout)
    out = json.dumps(bundle, indent=2, sort_keys=True, default=str)
    if args.output == "-":
        print(out)
    else:
        with open(args.output, "w") as f:
            f.write(out + "\n")
        errs = sum(1 for s in bundle["surfaces"].values()
                   if "error" in s)
        print(f"doctor: wrote {args.output} "
              f"({len(bundle['surfaces'])} surfaces, {errs} errors)")
    return 0


def cmd_diff(args) -> int:
    with open(args.a) as f:
        a = json.load(f)
    with open(args.b) as f:
        b = json.load(f)
    lines = diff_docs(_normalize(a), _normalize(b))
    for line in lines:
        print(line)
    print(f"doctor: {len(lines)} difference(s) "
          f"(volatile keys normalized)")
    return 1 if lines else 0


def cmd_baseline(args) -> int:
    with open(args.bundle) as f:
        bundle = json.load(f)
    verdicts = judge_bundle(bundle)
    width = max(len(c) for c, _s, _d in verdicts)
    bad = 0
    for check, status, detail in verdicts:
        if status == "FAIL":
            bad += 1
        print(f"{check:<{width}}  {status:<9} {detail}")
    print(f"doctor: {len(verdicts)} checks, {bad} failing")
    return 1 if bad else 0


def main(argv: Optional[List[str]] = None) -> int:
    p = argparse.ArgumentParser(
        prog="doctor.py", description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    sub = p.add_subparsers(dest="cmd", required=True)

    s = sub.add_parser("snapshot",
                       help="capture every debug surface into one "
                            "JSON bundle")
    s.add_argument("--base", default="http://localhost:10101",
                   help="server base URL")
    s.add_argument("-o", "--output", default="doctor-bundle.json",
                   help="output path ('-' for stdout)")
    s.add_argument("--timeout", type=float, default=10.0)
    s.set_defaults(fn=cmd_snapshot)

    d = sub.add_parser("diff", help="structural diff of two bundles")
    d.add_argument("a")
    d.add_argument("b")
    d.set_defaults(fn=cmd_diff)

    b = sub.add_parser("baseline",
                       help="judge a bundle: consistency checks")
    b.add_argument("bundle")
    b.set_defaults(fn=cmd_baseline)

    args = p.parse_args(argv)
    return args.fn(args)


if __name__ == "__main__":
    sys.exit(main())

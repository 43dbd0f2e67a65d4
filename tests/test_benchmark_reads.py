"""Every name a per-layer metric file takes from the PROGRAM is a name
the program publishes.

A case for each `benchmark/metrics/*.json` whose reader reads the
server's own snapshots (`/debug/vars`, `/internal/health`, `/info`:
`benchmark/harness/server.py: snapshot`): every series, label value and
path its `args` name is found in those three documents of a live CPU
server after one request of each family — or, where a small server on
the CPU cannot reach the path (the positions bank, a bank past the
resident limit), as a literal under `pilosa_tpu/`. A reader returns
nothing for a name the program does not publish, and the driver then
records a `null`: a rename or a deleted plane shows here, not in the
ledger. It reads `benchmark/` and edits nothing there.
"""

import glob
import json
import os
import re
import threading
import urllib.request
from datetime import datetime, timedelta

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# reader -> the arguments of it that name a histogram of /debug/vars,
# or a path into the snapshot.
HISTOGRAM_READERS = {"histogram_mean": ("name",),
                     "histogram_sum_share": ("part", "whole")}
PATH_READERS = {"counter_per_op": ("path",),
                "counter_delta": ("path",),
                "counter_scaled": ("path",),
                "counter_share": ("hits", "misses"),
                "counter_ratio": ("part", "whole")}


def _specs():
    out = {}
    for path in sorted(glob.glob(
            os.path.join(REPO, "benchmark", "metrics", "*.json"))):
        with open(path) as f:
            spec = json.load(f)
        if spec["reader"] in HISTOGRAM_READERS or \
                spec["reader"] in PATH_READERS or \
                spec["reader"] == "device_bytes":
            out[os.path.basename(path)[:-len(".json")]] = spec
    return out


SPECS = _specs()

# Stage histograms exist once a request has had the stage. These are the
# stages the small resident server below never has; they are held to the
# literal in the package instead.
LITERAL_ONLY = {
    "request.stage_seconds{stage:pbank.wave_wait}",
}

DAY0 = datetime(2019, 1, 1)


def _ts(day):
    return f"{DAY0 + timedelta(days=day):%Y-%m-%dT%H:%M}"


def _post(base, pql):
    req = urllib.request.Request(base + "/index/b/query",
                                 data=pql.encode(), method="POST")
    with urllib.request.urlopen(req, timeout=120) as r:
        return json.loads(r.read())["results"]


def _get(base, path):
    with urllib.request.urlopen(base + path, timeout=60) as r:
        return json.loads(r.read())


@pytest.fixture(scope="module")
def published(tmp_path_factory):
    """The three documents the harness snapshots, from a live server
    wired as `cmd_server` wires it (coalescer, runtime monitor, compile
    log, request records), after one request of each family."""
    from pilosa_tpu.core.field import FieldOptions
    from pilosa_tpu.core.holder import Holder
    from pilosa_tpu.ops.bitset import SHARD_WIDTH
    from pilosa_tpu.server import API, serve
    from pilosa_tpu.server.coalescer import QueryCoalescer
    from pilosa_tpu.utils.diagnostics import RuntimeMonitor
    from pilosa_tpu.utils.jaxenv import COMPILES
    from pilosa_tpu.utils.stats import MemStatsClient
    from pilosa_tpu.utils.timeline import TIMELINE

    h = Holder(str(tmp_path_factory.mktemp("reads")))
    h.open()
    idx = h.create_index("b")
    rng = np.random.default_rng(5)
    cols = np.unique(rng.integers(0, 2 * SHARD_WIDTH, 600)
                     ).astype(np.uint64)
    idx.create_field("f").import_bits(cols % 8, cols)
    idx.create_field("v", FieldOptions(type="int", min=0, max=1000)
                     ).import_values(cols, (cols % 997).astype(np.int64))
    t = idx.create_field("t", FieldOptions(type="time",
                                           time_quantum="D"))
    for d in range(40):
        day = cols[d::40]
        t.import_bits(np.ones(len(day), np.uint64), day,
                      [DAY0 + timedelta(days=d)] * len(day))
    idx.add_existence(cols)

    TIMELINE.reset()
    TIMELINE.configure(enabled=True, sample_every=1)
    stats = MemStatsClient()
    COMPILES.install(stats)
    mon = RuntimeMonitor(stats, interval=1000)
    mon.start()
    api = API(h, stats=stats)
    api.coalescer = QueryCoalescer(api.executor, window_s=0.05,
                                   max_batch=2, stats=stats)
    api.coalescer.start()
    srv = serve(api, "localhost", 0, background=True)
    base = f"http://localhost:{srv.server_address[1]}"
    try:
        topn = "TopN(f, Row(f=1), n=3)"
        for pql in (
                topn,
                "TopN(f, Row(f=1), n=3, tanimotoThreshold=10)",
                f"Count(Row(t=1, from='{_ts(0)}', to='{_ts(3)}'))",
                f"Count(Row(t=1, from='{_ts(0)}', to='{_ts(40)}'))",
                "GroupBy(Rows(f), aggregate=Sum(field=v))",
                "Count(Row(f=2))", "Count(Row(f=2))",  # a miss, a hit
                # A write, then a read of the bank it left stale.
                f"Set({int(cols[0]) + 1}, f=1)", topn):
            _post(base, pql)
        # Two requests in one flush: the test holds a request of its
        # own, so the first to arrive is not alone and waits the window.
        api.held.open()
        try:
            ts = [threading.Thread(target=_post,
                                   args=(base, f"Count(Row(f={r}))"))
                  for r in (3, 4)]
            for th in ts:
                th.start()
            for th in ts:
                th.join(timeout=120)
            assert not any(th.is_alive() for th in ts)
        finally:
            api.held.close()
        # The snapshot's own requests close their records after the
        # reply: ask twice, keep the second.
        _get(base, "/debug/vars")
        yield {"vars": _get(base, "/debug/vars"),
               "health": _get(base, "/internal/health"),
               "info": _get(base, "/info")}
    finally:
        srv.shutdown()
        srv.server_close()
        api.coalescer.stop()
        mon.stop()
        COMPILES.stats = None
        COMPILES.reset()
        TIMELINE.reset()
        h.close()


def _dig(doc, path):
    for key in path:
        if not isinstance(doc, dict) or key not in doc:
            return None
        doc = doc[key]
    return doc


def _stage_in_the_package(series):
    """A stage histogram is published from the span's own name
    (`TIMELINE.stage("x")` / `TIMELINE.phase("x")`): the quoted stage of
    `request.stage_seconds{stage:x}` as a literal under pilosa_tpu/."""
    stage = re.fullmatch(r"request\.stage_seconds\{stage:(.+)\}",
                         series).group(1)
    for root, _, files in os.walk(os.path.join(REPO, "pilosa_tpu")):
        for fname in files:
            if fname.endswith(".py"):
                with open(os.path.join(root, fname),
                          encoding="utf-8") as f:
                    if f'"{stage}"' in f.read():
                        return True
    return False


def test_the_cases_cover_the_readers_of_the_programs_own_names():
    assert len(SPECS) == 52


@pytest.mark.parametrize("metric", sorted(SPECS))
def test_the_program_publishes_what_the_metric_reads(published, metric):
    spec = SPECS[metric]
    reader, args = spec["reader"], spec["args"]
    if reader == "device_bytes":
        devices = published["info"]["devices"]
        assert devices and all(args["key"] in d for d in devices)
        return
    if reader in HISTOGRAM_READERS:
        wanted = [(["vars", "histograms", args[a]], args[a])
                  for a in HISTOGRAM_READERS[reader]]
    else:
        wanted = [(args[a], args[a][-1]) for a in PATH_READERS[reader]]
    for path, series in wanted:
        found = _dig(published, path)
        if series in LITERAL_ONLY:
            assert found is not None or \
                _stage_in_the_package(series), series
            continue
        assert found is not None, \
            f"{metric}: the server publishes no {'/'.join(path)}"
        if path[1] == "histograms":
            assert {"sum", "count"} <= set(found), (series, found)
        else:
            assert isinstance(found, (int, float)), (series, found)

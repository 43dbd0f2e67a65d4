"""The per-stage metrics PR 24 added: every new per_layer entry of
BENCHMARK.json resolves to a metric file and a reader, and the reader
returns a number from a recorded pair of /debug/vars snapshots of its
cell; on a program that publishes no such span or counter (the parent)
it returns nothing and does not raise."""

import json
import os

import pytest

from conftest import CHECKOUT
from harness.manifest import Manifest

HERE = os.path.dirname(os.path.abspath(__file__))
MAN = Manifest(CHECKOUT)
with open(os.path.join(HERE, "recorded_vars.json")) as f:
    RECORDED = json.load(f)["cells"]

STAGE_METRICS = {
    "http_read_mean_ms", "http_serialize_mean_ms", "http_write_mean_ms",
    "pql_parse_mean_ms", "coalescer_wait_mean_ms",
    "coalescer_flush_mean_ms", "plan_mean_ms", "dispatch_mean_ms",
    "d2h_wait_mean_ms", "finish_mean_ms", "d2h_bytes_per_op",
    "h2d_bytes_per_op", "xla_compiles_in_window",
    "xla_compile_s_in_window", "request_unaccounted_share"}
CELLS = {"sweep": "taxi-chip.topn-sweep", "point": "taxi-chip.point-serial"}
NEW = [m for m in MAN.doc["per_layer"]
       if m["name"].rsplit(".", 1)[0] in STAGE_METRICS]


def _read(name, ctx):
    spec = MAN.metric_spec(name)
    reader = MAN.load_module("readers", spec["reader"])
    return reader.read(ctx, **spec.get("args", {}))


def test_the_table_of_issue_24_is_all_there():
    names = {m["name"] for m in NEW}
    assert len(NEW) == 24 and len(names) == 24
    assert {n.rsplit(".", 1)[0] for n in names} == STAGE_METRICS
    for m in NEW:
        assert m["source"] in ("program_span", "program_counter")
        assert "workloads" not in m
        # Entries are appended: nothing the benchmark had moved.
        assert MAN.doc["per_layer"].index(m) >= 18
    # One data file per metric, over readers: two of them new.
    readers = {MAN.metric_spec(m["name"])["reader"] for m in NEW}
    assert readers == {"histogram_mean", "counter_delta",
                       "counter_per_op", "histogram_sum_share"}


@pytest.mark.parametrize("metric", NEW, ids=lambda m: m["name"])
def test_new_metric_reads_a_number_in_its_cell(metric):
    base, suffix = metric["name"].rsplit(".", 1)
    cell = CELLS[suffix]
    assert metric in MAN.metrics_for("per_layer", cell)
    other = CELLS["point" if suffix == "sweep" else "sweep"]
    assert metric not in MAN.metrics_for("per_layer", other)
    value = _read(metric["name"], RECORDED[cell])
    assert isinstance(value, (int, float)) and value >= 0, value
    if metric["unit"] == "%":
        assert value <= 100.0
    # A program without the span or counter: nothing, and no raise.
    empty = {"before": {"vars": {"histograms": {}, "counters": {}}},
             "after": {"vars": {"histograms": {}, "counters": {}}},
             "completed": 10}
    assert _read(metric["name"], empty) is None


def test_stage_means_add_up_to_the_request_mean():
    """On the direct path the top-level stage sums plus what no stage
    covers are the requests' total, to rounding: the stages tile."""
    ctx = RECORDED[CELLS["point"]]

    def dsum(key):
        a = ctx["after"]["vars"]["histograms"].get(key)
        b = ctx["before"]["vars"]["histograms"].get(
            key, {"sum": 0.0, "count": 0})
        return (a["sum"] - b["sum"]) if a else 0.0

    top = ["http.read", "pql.parse", "coalescer.wait", "cache.lookup",
           "plan", "h2d", "dispatch", "d2h", "finish", "http.serialize",
           "http.write"]
    staged = sum(dsum(f"request.stage_seconds{{stage:{s}}}") for s in top)
    total = dsum("request.total_seconds")
    assert total > 0
    assert staged + dsum("request.unaccounted_seconds") == \
        pytest.approx(total, rel=0.02)
    share = _read("request_unaccounted_share.point", ctx)
    assert share == pytest.approx(
        100 * dsum("request.unaccounted_seconds") / total)


def test_per_op_and_share_readers_arithmetic():
    per_op = MAN.load_module("readers", "counter_per_op")
    share = MAN.load_module("readers", "histogram_sum_share")
    ctx = {"before": {"vars": {"counters": {"c": 100},
                               "histograms": {"p": {"sum": 1.0},
                                              "w": {"sum": 10.0}}}},
           "after": {"vars": {"counters": {"c": 400},
                              "histograms": {"p": {"sum": 2.0},
                                             "w": {"sum": 30.0}}}},
           "completed": 30}
    assert per_op.read(ctx, ["vars", "counters", "c"]) == 10.0
    assert per_op.read(dict(ctx, completed=0),
                       ["vars", "counters", "c"]) is None
    assert per_op.read(ctx, ["vars", "counters", "nope"]) is None
    assert share.read(ctx, "p", "w") == pytest.approx(5.0)
    assert share.read(ctx, "p", "nope") is None
    flat = dict(ctx, after=ctx["before"])
    assert share.read(flat, "p", "w") is None      # nothing moved

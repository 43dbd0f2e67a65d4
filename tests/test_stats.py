"""prometheus_text exposition invariants (utils/stats.py) — label
escaping, the `_size` no-`_seconds`-suffix rule, and the one-TYPE-line-
per-metric invariant — plus StatsdStatsClient.close() thread join."""

import threading

from pilosa_tpu.utils.stats import (
    MemStatsClient, StatsdStatsClient, prometheus_text,
)


def test_prom_label_escaping():
    """Tag values with backslashes and double quotes must escape per
    the text exposition format, never break the label syntax."""
    stats = MemStatsClient()
    stats.with_tags('path:C:\\tmp', 'msg:say "hi"').count("esc", 2)
    out = prometheus_text(stats)
    line = next(l for l in out.splitlines()
                if l.startswith("pilosa_esc_total{"))
    assert 'path="C:\\\\tmp"' in line
    assert 'msg="say \\"hi\\""' in line
    assert line.endswith(" 2")


def test_prom_size_metrics_have_no_seconds_suffix():
    """Unitless distributions must not claim seconds: histograms
    (batch/group sizes) export bare _bucket/_sum/_count names, and a
    `*_size` timing stays suffix-free too."""
    stats = MemStatsClient()
    stats.histogram("coalescer.batch_size", 4)
    stats.timing("queue.wait_size", 3)
    stats.timing("coalescer.request", 0.25)
    out = prometheus_text(stats)
    assert 'pilosa_coalescer_batch_size_bucket{le="4"} 1' in out
    assert "pilosa_coalescer_batch_size_seconds" not in out
    assert "pilosa_queue_wait_size{" in out
    assert "pilosa_queue_wait_size_seconds" not in out
    assert "pilosa_coalescer_request_seconds{" in out


def test_prom_histogram_bucket_invariants():
    """fusion_group_size is a REAL cumulative histogram: fixed pow2
    buckets 1,2,4,...,64,+Inf; _bucket counts monotone non-decreasing;
    le="+Inf" == _count; _sum is the observation total."""
    stats = MemStatsClient()
    for v in (1, 1, 2, 3, 5, 64, 200):
        stats.histogram("executor.fusion_group_size", v)
    snap = stats.snapshot()["histograms"]["executor.fusion_group_size"]
    assert list(snap["buckets"]) == ["1", "2", "4", "8", "16", "32",
                                     "64", "+Inf"]
    # Cumulative counts: 2 at le=1, +1 at le=2, +1 at le=4 (v=3),
    # +1 at le=8 (v=5), +1 at le=64, +1 only past every bound (v=200).
    assert snap["buckets"] == {"1": 2, "2": 3, "4": 4, "8": 5,
                               "16": 5, "32": 5, "64": 6, "+Inf": 7}
    cum = list(snap["buckets"].values())
    assert cum == sorted(cum)  # monotone non-decreasing
    assert snap["count"] == snap["buckets"]["+Inf"] == 7
    assert snap["sum"] == 1 + 1 + 2 + 3 + 5 + 64 + 200

    out = prometheus_text(stats)
    assert "# TYPE pilosa_executor_fusion_group_size histogram" in out
    assert 'pilosa_executor_fusion_group_size_bucket{le="+Inf"} 7' in out
    assert "pilosa_executor_fusion_group_size_count 7" in out
    assert "pilosa_executor_fusion_group_size_sum 276" in out


def test_prom_histogram_labels_ride_buckets():
    """A tagged histogram keeps its labels beside le= on every bucket
    line (tags must not fold into the metric name)."""
    stats = MemStatsClient()
    stats.with_tags("index:i1").histogram("executor.fusion_group_size", 2)
    out = prometheus_text(stats)
    assert ('pilosa_executor_fusion_group_size_bucket'
            '{index="i1",le="2"} 1') in out
    assert 'pilosa_executor_fusion_group_size_count{index="i1"} 1' in out


def test_prom_one_type_line_per_metric():
    stats = MemStatsClient()
    stats.count("q", 1)
    stats.with_tags("index:a").count("q", 1)
    stats.with_tags("index:b").count("q", 1)
    stats.gauge("depth", 3)
    stats.with_tags("index:a").gauge("depth", 5)
    stats.timing("lat", 0.1)
    stats.with_tags("index:a").timing("lat", 0.2)
    out = prometheus_text(stats)
    type_lines = [l for l in out.splitlines() if l.startswith("# TYPE ")]
    names = [l.split()[2] for l in type_lines]
    assert len(names) == len(set(names)), names
    # Every series name that appears has exactly one TYPE declaration.
    assert names.count("pilosa_q_total") == 1
    assert names.count("pilosa_depth") == 1
    assert names.count("pilosa_lat_seconds") == 1
    # Samples with different label sets still share the one TYPE line.
    q_samples = [l for l in out.splitlines()
                 if l.startswith("pilosa_q_total")]
    assert len(q_samples) == 3


def test_prom_families_stay_contiguous_under_name_interleave():
    """The exposition format requires every family's samples to form
    ONE contiguous group under exactly one # TYPE line. Raw-key
    sorting breaks that whenever another family name sorts between a
    family's untagged and tagged spellings ('fragment.reads' <
    'fragment.reads_dedup' < 'fragment.reads{index=...}' since
    '_' < '{') — the second group then rode TYPE-less behind a
    different family. Families must group by name, not by raw key."""
    stats = MemStatsClient()
    stats.count("fragment.reads", 7)
    stats.count("fragment.reads_dedup", 1)  # sorts BETWEEN the two
    stats.with_tags("index:i1").count("fragment.reads", 3)
    out = prometheus_text(stats)
    lines = out.splitlines()
    fam = [i for i, l in enumerate(lines)
           if l.startswith("pilosa_fragment_reads_total")
           or l == "# TYPE pilosa_fragment_reads_total counter"]
    # TYPE + both samples, contiguous.
    assert len(fam) == 3
    assert fam == list(range(fam[0], fam[0] + 3))
    assert lines[fam[0]] == "# TYPE pilosa_fragment_reads_total counter"
    type_lines = [l.split()[2] for l in lines
                  if l.startswith("# TYPE ")]
    assert type_lines.count("pilosa_fragment_reads_total") == 1


def test_prom_new_workload_counter_families():
    """The workload-plane counter families export with one TYPE line
    each and proper label escaping (the invariants of this module
    extended to pilosa_fragment_{reads,writes}_total and
    pilosa_query_repeat_ratio)."""
    stats = MemStatsClient()
    stats.count("fragment.reads", 5)
    stats.with_tags('index:a"b').count("fragment.reads", 2)
    stats.count("fragment.writes", 4)
    stats.gauge("query.repeat_ratio", 0.9375)
    out = prometheus_text(stats)
    lines = out.splitlines()
    for fam, typ in (("pilosa_fragment_reads_total", "counter"),
                     ("pilosa_fragment_writes_total", "counter"),
                     ("pilosa_query_repeat_ratio", "gauge")):
        types = [l for l in lines if l == f"# TYPE {fam} {typ}"]
        assert len(types) == 1, (fam, out)
        # Samples directly follow their single TYPE line.
        i = lines.index(types[0])
        assert lines[i + 1].startswith(fam), (fam, lines[i:i + 2])
    assert "pilosa_fragment_reads_total 5" in out
    assert 'pilosa_fragment_reads_total{index="a\\"b"} 2' in out
    assert "pilosa_query_repeat_ratio 0.9375" in out


def test_prom_tagged_names_stay_bounded():
    """Tags become labels, never part of the metric name (cardinality
    control)."""
    stats = MemStatsClient()
    stats.with_tags("index:i1").count("query", 1)
    out = prometheus_text(stats)
    assert 'pilosa_query_total{index="i1"} 1' in out
    assert "i1_total" not in out


def test_statsd_close_joins_flush_thread():
    """close() must stop AND join the periodic flush thread (it was
    previously a fire-and-forget daemon that could race the final
    flush)."""
    before = threading.active_count()
    c = StatsdStatsClient("localhost:1")  # UDP, nothing listening
    t = c._shared["thread"]
    assert t.is_alive()
    c.count("x", 1)
    c.close()
    t.join(timeout=5)
    assert not t.is_alive()
    assert threading.active_count() <= before + 1


def test_statsd_close_via_tagged_clone():
    """with_tags clones share the flush thread; close() through a clone
    stops it too."""
    c = StatsdStatsClient("localhost:1")
    clone = c.with_tags("a:b")
    clone.close()
    assert not c._shared["thread"].is_alive() or \
        c._shared["thread"].join(timeout=5) is None
    assert c._shared["stop"].is_set()


def test_snapshot_reads_its_sources_and_forgets_a_removed_one():
    stats = MemStatsClient()
    stats.count("own", 1)
    held_back = [0.5]

    def source():
        # A source may observe what it held back on the way: it is
        # called outside the client's lock.
        while held_back:
            stats.with_tags("gen:2").histogram(
                "runtime.gc_pause_seconds", held_back.pop(),
                buckets=(0.1, 1.0))
        return {"runtime.cpu_seconds": 1.5}

    stats.with_tags("x:y").add_source(source)     # lands on the root
    snap = stats.snapshot()
    assert snap["counters"] == {"own": 1, "runtime.cpu_seconds": 1.5}
    assert snap["histograms"]["runtime.gc_pause_seconds{gen:2}"] == {
        "buckets": {"0.1": 0, "1": 1, "+Inf": 1}, "sum": 0.5, "count": 1}
    stats.remove_source(source)
    stats.remove_source(source)                   # twice is harmless
    assert stats.snapshot()["counters"] == {"own": 1}

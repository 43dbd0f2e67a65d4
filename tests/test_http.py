"""HTTP surface tests (reference server/handler_test.go
TestHandler_Endpoints) — a real server on a random port."""

import json
import urllib.error
import urllib.request

import numpy as np
import pytest

from pilosa_tpu.storage import Bitmap


@pytest.fixture
def server(live_server):
    base, api, _h = live_server
    yield base, api


def req(base, method, path, body=None, raw=False):
    data = None
    if body is not None:
        data = body if isinstance(body, bytes) else json.dumps(body).encode()
    r = urllib.request.Request(base + path, data=data, method=method)
    with urllib.request.urlopen(r) as resp:
        payload = resp.read()
        return resp.status, payload if raw else json.loads(payload or b"{}")


def test_end_to_end_http(server):
    base, _ = server
    # create index + fields
    st, _ = req(base, "POST", "/index/myidx", {"options": {}})
    assert st == 200
    st, _ = req(base, "POST", "/index/myidx/field/f", {"options": {}})
    assert st == 200
    st, _ = req(base, "POST", "/index/myidx/field/n",
                {"options": {"type": "int", "min": 0, "max": 100}})
    assert st == 200

    # write + query via PQL
    st, res = req(base, "POST", "/index/myidx/query",
                  b"Set(1, f=10) Set(2, f=10) Set(1, n=42)")
    assert res["results"] == [True, True, True]
    st, res = req(base, "POST", "/index/myidx/query", b"Row(f=10)")
    assert res["results"][0]["columns"] == [1, 2]
    st, res = req(base, "POST", "/index/myidx/query",
                  {"query": "Count(Row(f=10))"})
    assert res["results"] == [2]
    st, res = req(base, "POST", "/index/myidx/query", b"TopN(f, n=1)")
    assert res["results"][0] == [{"id": 10, "count": 2}]
    st, res = req(base, "POST", "/index/myidx/query", b'Sum(field="n")')
    assert res["results"][0] == {"value": 42, "count": 1}

    # bulk import (JSON body)
    st, _ = req(base, "POST", "/index/myidx/field/f/import",
                {"rowIDs": [7, 7], "columnIDs": [100, 200]})
    assert st == 200
    st, res = req(base, "POST", "/index/myidx/query", b"Row(f=7)")
    assert res["results"][0]["columns"] == [100, 200]

    # roaring import (raw bytes)
    bm = Bitmap(np.array([3 * 2**20 + 5], dtype=np.uint64))  # row 3, col 5
    st, _ = req(base, "POST", "/index/myidx/field/f/import-roaring/0",
                bm.write_bytes())
    st, res = req(base, "POST", "/index/myidx/query", b"Row(f=3)")
    assert res["results"][0]["columns"] == [5]

    # schema / status / version / shards-max
    st, schema = req(base, "GET", "/schema")
    names = [f["name"] for f in schema["indexes"][0]["fields"]]
    assert names == ["f", "n"]
    st, status = req(base, "GET", "/status")
    assert status["state"] == "NORMAL"
    st, v = req(base, "GET", "/version")
    assert "version" in v
    st, sm = req(base, "GET", "/internal/shards/max")
    assert sm["standard"]["myidx"] == 0

    # export + fragment sync endpoints
    st, csv = req(base, "GET", "/export?index=myidx&field=f&shard=0", raw=True)
    assert b"10,1" in csv
    st, blocks = req(base, "GET",
                     "/internal/fragment/blocks?index=myidx&field=f&shard=0")
    assert blocks["blocks"]
    st, frag = req(base, "GET",
                   "/internal/fragment/data?index=myidx&field=f&shard=0",
                   raw=True)
    got = Bitmap.from_bytes(frag)
    assert got.count() > 0

    # delete field then index
    st, _ = req(base, "DELETE", "/index/myidx/field/n")
    st, schema = req(base, "GET", "/schema")
    assert [f["name"] for f in schema["indexes"][0]["fields"]] == ["f"]
    st, _ = req(base, "DELETE", "/index/myidx")
    st, schema = req(base, "GET", "/schema")
    assert schema["indexes"] == []


@pytest.mark.parametrize("path", ["/debug/roofline", "/debug/history",
                                  "/debug/slo", "/cluster/slo"])
def test_no_route_serves_a_host_clock_under_a_device_name(server, path):
    """The recorder and the sentinel went with their routes: a path
    that served one is a 404 like any unknown path, and folds into the
    `other` endpoint label."""
    from pilosa_tpu.server.http import endpoint_label
    base, _ = server
    with pytest.raises(urllib.error.HTTPError) as e:
        req(base, "GET", path)
    assert e.value.code == 404
    assert endpoint_label(path) == "other"


def test_http_errors(server):
    base, _ = server
    with pytest.raises(urllib.error.HTTPError) as e:
        req(base, "POST", "/index/nosuch/query", b"Row(f=1)")
    assert e.value.code == 404 or e.value.code == 400
    with pytest.raises(urllib.error.HTTPError) as e:
        req(base, "GET", "/no/such/route")
    assert e.value.code == 404
    with pytest.raises(urllib.error.HTTPError) as e:
        req(base, "DELETE", "/index/nosuch")
    assert e.value.code == 404
    # malformed PQL
    req(base, "POST", "/index/i2", {})
    with pytest.raises(urllib.error.HTTPError) as e:
        req(base, "POST", "/index/i2/query", b"Row(")
    assert e.value.code == 400


def test_column_keys_http(server):
    base, _ = server
    req(base, "POST", "/index/keyed", {"options": {"keys": True}})
    req(base, "POST", "/index/keyed/field/f",
        {"options": {"keys": True}})
    st, res = req(base, "POST", "/index/keyed/query",
                  b"Set('alice', f='admin') Set('bob', f='admin')")
    assert res["results"] == [True, True]
    st, res = req(base, "POST", "/index/keyed/query", b"Row(f='admin')")
    assert sorted(res["results"][0]["keys"]) == ["alice", "bob"]
    # import with keys
    st, _ = req(base, "POST", "/index/keyed/field/f/import",
                {"rowKeys": ["user"], "columnKeys": ["carol"]})
    st, res = req(base, "POST", "/index/keyed/query", b"Row(f='user')")
    assert res["results"][0]["keys"] == ["carol"]


def test_translation_scoping(server):
    """Attr values never get key-translated; unkeyed fields reject string
    rows; keys stay aligned with columns."""
    base, api = server
    req(base, "POST", "/index/k2", {"options": {"keys": True}})
    req(base, "POST", "/index/k2/field/city", {"options": {"keys": True}})
    req(base, "POST", "/index/k2/field/plain", {"options": {}})
    # attr named like a keyed field must stay a string
    req(base, "POST", "/index/k2/query",
        b"Set('c1', plain=1) SetRowAttrs(plain, 1, city=\"nyc\")")
    assert api.holder.index("k2").field("plain").row_attr_store.get(1) == \
        {"city": "nyc"}
    # string row on unkeyed field errors instead of silently allocating
    with pytest.raises(urllib.error.HTTPError) as e:
        req(base, "POST", "/index/k2/query", b"Row(plain='oops')")
    assert e.value.code == 400
    # raw ids on a keyed field are rejected unless explicitly allowed
    # (reference api.go:836-860 + ignoreKeyCheck escape hatch)
    with pytest.raises(urllib.error.HTTPError) as e:
        req(base, "POST", "/index/k2/field/city/import",
            {"rowIDs": [1], "columnIDs": [99]})
    assert e.value.code == 400
    # keys align with columns even for raw-id imports
    req(base, "POST", "/index/k2/field/city/import?ignoreKeyCheck=true",
        {"rowIDs": [1], "columnIDs": [99]})  # bypasses the translator
    req(base, "POST", "/index/k2/query", b"Set('alice', city='a')")
    st, res = req(base, "POST", "/index/k2/query",
                  b"Union(Row(city='a'), Row(city=1))")
    r = res["results"][0]
    assert len(r["keys"]) == len(r["columns"])


def test_rows_previous_key(server):
    base, _ = server
    req(base, "POST", "/index/k3", {"options": {"keys": True}})
    req(base, "POST", "/index/k3/field/f", {"options": {"keys": True}})
    req(base, "POST", "/index/k3/query",
        b"Set('c1', f='apple') Set('c2', f='banana')")
    st, res = req(base, "POST", "/index/k3/query", b"Rows(f, previous='apple')")
    assert res["results"][0]["keys"] == ["banana"]


def test_query_url_exec_options(server):
    """columnAttrs/excludeColumns as URL args, reference PostQuery
    optional args (http/handler.go:186)."""
    base, _ = server
    req(base, "POST", "/index/u", {})
    req(base, "POST", "/index/u/field/f", {})
    req(base, "POST", "/index/u/query", b"Set(7, f=1)")
    st, res = req(base, "POST", "/index/u/query?excludeColumns=true",
                  b"Row(f=1)")
    assert st == 200 and res["results"][0]["columns"] == []
    st, res = req(base, "POST", "/index/u/query", b"Row(f=1)")
    assert res["results"][0]["columns"] == [7]


def test_unknown_query_args_rejected(server):
    """Unknown query-string args get 400 (reference queryArgValidator,
    http/handler.go:171-235)."""
    import urllib.error
    base, _ = server
    req(base, "POST", "/index/v", {})
    req(base, "POST", "/index/v/field/f", {})
    with pytest.raises(urllib.error.HTTPError) as ei:
        req(base, "POST", "/index/v/query?bogus=1", b"Count(Row(f=1))")
    assert ei.value.code == 400
    with pytest.raises(urllib.error.HTTPError) as ei:
        req(base, "GET", "/export?index=v&field=f&bad=2")
    assert ei.value.code == 400


def test_export_csv_translates_keys(server):
    """Export writes keys, not raw ids, for keyed fields/indexes
    (reference api.ExportCSV per-bit translation, api.go:430-500)."""
    base, _ = server
    req(base, "POST", "/index/ek", {"options": {"keys": True}})
    req(base, "POST", "/index/ek/field/tag", {"options": {"keys": True}})
    req(base, "POST", "/index/ek/query", b"Set('alice', tag='red')")
    st, body = req(base, "GET", "/export?index=ek&field=tag&shard=0",
                   raw=True)
    assert st == 200 and body.decode().strip() == "red,alice"


def test_export_csv_quoting_and_fallback(server):
    """Keys with commas are csv-quoted; unmapped ids fall back to the
    decimal id instead of 'None'."""
    base, _ = server
    req(base, "POST", "/index/eq", {"options": {"keys": True}})
    req(base, "POST", "/index/eq/field/tag", {"options": {"keys": True}})
    req(base, "POST", "/index/eq/query", b"Set('a,b', tag='red')")
    # raw-id bit with no key mapping, via the escape hatch
    req(base, "POST", "/index/eq/field/tag/import?ignoreKeyCheck=true",
        {"rowIDs": [55], "columnIDs": [7]})
    st, body = req(base, "GET", "/export?index=eq&field=tag&shard=0",
                   raw=True)
    lines = sorted(body.decode().strip().split("\n"))
    assert 'red,"a,b"' in lines
    assert "55,7" in lines


def test_parse_error_with_url_options_is_400(server):
    base, _ = server
    req(base, "POST", "/index/pe", {})
    with pytest.raises(urllib.error.HTTPError) as e:
        req(base, "POST", "/index/pe/query?excludeColumns=true", b"Row(")
    assert e.value.code == 400
    # boolean URL args: explicit false stays off
    req(base, "POST", "/index/pe/field/f", {})
    req(base, "POST", "/index/pe/query", b"Set(3, f=1)")
    st, res = req(base, "POST", "/index/pe/query?excludeColumns=false",
                  b"Row(f=1)")
    assert res["results"][0]["columns"] == [3]


def test_prometheus_metrics_endpoint(server):
    base, _ = server
    req(base, "POST", "/index/pm", {})
    req(base, "POST", "/index/pm/field/f", {})
    req(base, "POST", "/index/pm/query", b"Set(1, f=2)")
    req(base, "POST", "/index/pm/query", b"Count(Row(f=2))")
    st, body = req(base, "GET", "/metrics", raw=True)
    text = body.decode()
    assert st == 200
    assert "# TYPE pilosa_query_total counter" in text
    assert "pilosa_query_total" in text

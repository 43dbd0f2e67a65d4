"""CLI tests (reference cmd/*_test.go / ctl tests)."""

import os

import pytest

from pilosa_tpu.cli.main import main
from pilosa_tpu.utils.config import Config, load_config


def test_generate_config_roundtrip(tmp_path, capsys):
    assert main(["generate-config"]) == 0
    toml_text = capsys.readouterr().out
    p = tmp_path / "cfg.toml"
    p.write_text(toml_text)
    cfg = load_config(str(p))
    assert cfg == Config()


def test_config_precedence(tmp_path, monkeypatch):
    p = tmp_path / "cfg.toml"
    p.write_text('bind = "localhost:7777"\nverbose = true\n')
    cfg = load_config(str(p))
    assert cfg.port == 7777 and cfg.verbose
    monkeypatch.setenv("PILOSA_TPU_BIND", "localhost:8888")
    cfg = load_config(str(p))
    assert cfg.port == 8888  # env beats file
    cfg = load_config(str(p), {"bind": "localhost:9999"})
    assert cfg.port == 9999  # flags beat env
    with pytest.raises(ValueError, match="unknown config key"):
        bad = tmp_path / "bad.toml"
        bad.write_text('no_such_key = 1\n')
        load_config(str(bad))


@pytest.mark.parametrize("toml_text, key", [
    ("[roofline]\ngbps = 819.0\n", "roofline.'gbps'"),
    ("[sentinel]\nring = 360\n", "sentinel.'ring'"),
    ('[slo]\nquery = "99.9% < 25ms"\n', "slo.'query'"),
    ("[profile]\nsample_every = 100\n", "profile.'sample_every'"),
    ("profile_sample_every = 100\n", "'profile_sample_every'"),
])
def test_a_config_for_a_deleted_plane_is_refused_by_name(
        tmp_path, toml_text, key):
    """No compatibility shim: a file that still configures the roofline
    recorder, the sentinel, an objective or the sampled fence meets the
    loader's rule for any unknown key, and the error names it."""
    p = tmp_path / "old.toml"
    p.write_text(toml_text)
    with pytest.raises(ValueError, match="unknown config key") as e:
        load_config(str(p))
    assert key in str(e.value)


def test_import_export_check_inspect(tmp_path, capsys):
    csv_file = tmp_path / "data.csv"
    csv_file.write_text("1,10\n1,20\n2,10\n")
    data_dir = str(tmp_path / "data")
    assert main(["import", "-d", data_dir, "-i", "idx", "-f", "f",
                 str(csv_file)]) == 0
    out_file = tmp_path / "out.csv"
    assert main(["export", "-d", data_dir, "-i", "idx", "-f", "f",
                 "-o", str(out_file)]) == 0
    got = sorted(out_file.read_text().strip().split("\n"))
    assert got == ["1,10", "1,20", "2,10"]

    frag = os.path.join(data_dir, "idx", "f", "views", "standard",
                        "fragments", "0")
    assert main(["check", frag]) == 0
    assert "ok" in capsys.readouterr().out
    assert main(["inspect", frag, "--verbose"]) == 0
    out = capsys.readouterr().out
    assert "2 rows" in out

    # corrupt file detected
    bad = tmp_path / "bad.bin"
    bad.write_bytes(b"\x00\x01\x02")
    assert main(["check", str(bad)]) == 1


def test_fold_rewrites_to_pure_snapshot(tmp_path, capsys):
    """`fold` rewrites a fragment with OP_ADD_ROARING extension records
    as a pure reference-format snapshot (ADVICE r3: the downgrade path
    for the one-way data-file compatibility, docs/parity.md)."""
    from pilosa_tpu.storage.roaring import Bitmap

    csv_file = tmp_path / "data.csv"
    csv_file.write_text("1,10\n1,20\n2,10\n7,999999\n")
    data_dir = str(tmp_path / "data")
    assert main(["import", "-d", data_dir, "-i", "idx", "-f", "f",
                 str(csv_file)]) == 0
    frag = os.path.join(data_dir, "idx", "f", "views", "standard",
                        "fragments", "0")
    with open(frag, "rb") as f:
        before = Bitmap.from_bytes(f.read())
    # The bulk import path appends the extension record the reference
    # cannot read — the precondition that makes fold necessary.
    assert before.op_n > 0
    want = before.count()

    assert main(["fold", frag]) == 0
    assert "folded" in capsys.readouterr().out
    with open(frag, "rb") as f:
        raw = f.read()
    after = Bitmap.from_bytes(raw)
    assert after.op_n == 0 and after.count() == want
    # No op records remain at all: the snapshot section spans the file.
    assert after.snapshot_bytes == len(raw) and after.oplog_bytes == 0
    # Idempotent, and the folded holder still answers queries.
    assert main(["fold", frag]) == 0
    from pilosa_tpu.executor import Executor
    from pilosa_tpu.core.holder import Holder
    h = Holder(data_dir)
    h.open()
    (res,) = Executor(h).execute("idx", "Row(f=7)")
    assert res.columns() == [999999]
    h.close()


def test_fold_force_sidecars_torn_tail(tmp_path, capsys):
    """fold refuses a torn-tail file without --force; with --force it
    preserves the dropped bytes in a .torn sidecar (the same
    never-destroy-bytes rule as Fragment.open) before rewriting."""
    from pilosa_tpu.storage.roaring import Bitmap, encode_op, OP_ADD

    b = Bitmap()
    b.add(5)
    b.add(70000)
    torn = encode_op(OP_ADD, 123)[:-2]  # record truncated mid-checksum
    frag = tmp_path / "frag"
    frag.write_bytes(b.write_bytes() + torn)

    assert main(["fold", str(frag)]) == 1
    assert "--force" in capsys.readouterr().err
    assert main(["fold", str(frag), "--force"]) == 0
    err = capsys.readouterr().err
    assert "sidecarred" in err
    assert (tmp_path / "frag.torn").read_bytes() == torn
    after = Bitmap.from_bytes(frag.read_bytes())
    assert after.op_n == 0 and after.count() == 2


def test_import_int_field(tmp_path, capsys):
    csv_file = tmp_path / "vals.csv"
    csv_file.write_text("1,100\n2,-5\n3,40\n")
    data_dir = str(tmp_path / "data")
    assert main(["import", "-d", data_dir, "-i", "idx", "-f", "n",
                 "--field-type", "int", str(csv_file)]) == 0
    from pilosa_tpu.core.holder import Holder
    h = Holder(data_dir)
    h.open()
    assert h.index("idx").field("n").value(2) == (-5, True)
    h.close()


def test_import_remote_host(tmp_path, live_server, capsys):
    """`import --host` posts CSV batches through a running server's
    import API, creating the schema if missing (reference ctl/import.go
    remote mode; VERDICT r3 missing #5)."""
    base, api, holder = live_server
    csv_file = tmp_path / "r.csv"
    csv_file.write_text("1,5\n1,6\n2,5\n")
    assert main(["import", "--host", base, "-i", "ri", "-f", "f",
                 str(csv_file)]) == 0
    assert "via" in capsys.readouterr().out
    (res,) = api.executor.execute("ri", "Count(Row(f=1))")
    assert res == 2
    # Int-field variant creates the field with a fitting range.
    vals = tmp_path / "v.csv"
    vals.write_text("1,100\n2,-7\n")
    assert main(["import", "--host", base, "-i", "ri", "-f", "n",
                 "--field-type", "int", str(vals)]) == 0
    assert holder.index("ri").field("n").value(2) == (-7, True)
    # Re-import into the existing schema is fine (ensure tolerates 409).
    assert main(["import", "--host", base, "-i", "ri", "-f", "f",
                 str(csv_file)]) == 0
    # Neither --host nor --data-dir is an error, not a crash.
    assert main(["import", "-i", "x", "-f", "f", str(csv_file)]) == 2


def test_backup_restore_roundtrip(tmp_path, capsys):
    """backup tars the data dir; restore unpacks it; the restored holder
    answers the same query (offline analog of the reference's tar-stream
    backup, fragment.go:1885-2230)."""
    src = str(tmp_path / "src")
    csvf = tmp_path / "in.csv"
    csvf.write_text("1,5\n1,9\n2,5\n")
    assert main(["import", "-d", src, "-i", "idx", "-f", "f",
                 str(csvf)]) == 0
    tar = str(tmp_path / "bk.tgz")
    assert main(["backup", "-d", src, "-o", tar]) == 0
    dst = str(tmp_path / "dst")
    assert main(["restore", "-d", dst, "-i", tar]) == 0
    out1 = str(tmp_path / "a.csv")
    out2 = str(tmp_path / "b.csv")
    assert main(["export", "-d", src, "-i", "idx", "-f", "f",
                 "-o", out1]) == 0
    assert main(["export", "-d", dst, "-i", "idx", "-f", "f",
                 "-o", out2]) == 0
    assert open(out1).read() == open(out2).read() != ""
    # refuse restore into non-empty without --force
    assert main(["restore", "-d", dst, "-i", tar]) == 1
    assert main(["restore", "-d", dst, "-i", tar, "--force"]) == 0


def test_restore_force_replaces_and_rejects_bad_members(tmp_path):
    """--force replaces (post-backup files don't survive); symlink
    members are rejected before extraction."""
    import tarfile
    src = str(tmp_path / "s")
    csvf = tmp_path / "in.csv"
    csvf.write_text("1,5\n")
    assert main(["import", "-d", src, "-i", "idx", "-f", "f",
                 str(csvf)]) == 0
    tar = str(tmp_path / "bk.tgz")
    assert main(["backup", "-d", src, "-o", tar]) == 0
    dst = tmp_path / "d"
    assert main(["restore", "-d", str(dst), "-i", tar]) == 0
    stray = dst / "idx" / "stray.bin"
    stray.write_text("post-backup junk")
    assert main(["restore", "-d", str(dst), "-i", tar, "--force"]) == 0
    assert not stray.exists()  # replaced, not merged
    # symlink member refused up front
    evil = str(tmp_path / "evil.tgz")
    with tarfile.open(evil, "w:gz") as t:
        info = tarfile.TarInfo("link")
        info.type = tarfile.SYMTYPE
        info.linkname = "/etc/passwd"
        t.addfile(info)
    empty = str(tmp_path / "e")
    assert main(["restore", "-d", empty, "-i", evil]) == 1


def test_backup_output_inside_data_dir(tmp_path):
    src = tmp_path / "s"
    csvf = tmp_path / "in.csv"
    csvf.write_text("1,5\n")
    assert main(["import", "-d", str(src), "-i", "idx", "-f", "f",
                 str(csvf)]) == 0
    tar = str(src / "bk.tgz")
    assert main(["backup", "-d", str(src), "-o", tar]) == 0
    import tarfile
    with tarfile.open(tar) as t:
        assert "bk.tgz" not in t.getnames()

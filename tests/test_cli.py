"""CLI command tests (reference cmd/*_test.go + ctl/*_test.go), driven
in-process against a real server on a random port."""

import io
import os

import pytest

from pilosa_tpu.cli.commands import main
from pilosa_tpu.server.server import Server


@pytest.fixture
def server(tmp_path):
    s = Server(str(tmp_path / "data"), host="127.0.0.1:0",
               anti_entropy_interval=0, polling_interval=0)
    s.open()
    yield s
    s.close()


def run(argv):
    out, err = io.StringIO(), io.StringIO()
    rc = main(argv, stdout=out, stderr=err)
    return rc, out.getvalue(), err.getvalue()


def setup_schema(server, index="i", frame="f"):
    idx = server.holder.create_index_if_not_exists(index)
    idx.create_frame_if_not_exists(frame)


class TestImportExportSort:
    def test_import_then_export(self, server, tmp_path):
        setup_schema(server)
        csv_file = tmp_path / "bits.csv"
        csv_file.write_text("1,10\n1,11\n2,10\n\n")
        rc, out, err = run(["import", "--host", server.host,
                            "-i", "i", "-f", "f", str(csv_file)])
        assert rc == 0, err
        rc, out, err = run(["export", "--host", server.host,
                            "-i", "i", "-f", "f"])
        assert rc == 0
        assert out.splitlines() == ["1,10", "1,11", "2,10"]

    def test_import_with_timestamp(self, server, tmp_path):
        setup_schema(server)
        idx = server.holder.index("i")
        idx.delete_frame("f")
        from pilosa_tpu.models.frame import FrameOptions
        idx.create_frame_if_not_exists("f", FrameOptions(time_quantum="Y"))
        csv_file = tmp_path / "bits.csv"
        csv_file.write_text("1,10,2017-03-04T10:30\n")
        rc, _, err = run(["import", "--host", server.host,
                          "-i", "i", "-f", "f", str(csv_file)])
        assert rc == 0, err
        assert "standard_2017" in server.holder.frame("i", "f").views

    def test_import_multislice_groups(self, server, tmp_path):
        """The vectorized import path must group by slice exactly like
        Bits.GroupBySlice (client.go:1027-1040)."""
        setup_schema(server)
        from pilosa_tpu import SLICE_WIDTH
        csv_file = tmp_path / "m.csv"
        csv_file.write_text(f"1,5\n1,{SLICE_WIDTH + 5}\n"
                            f"7,{2 * SLICE_WIDTH + 3}\n1,6\n")
        rc, _, err = run(["import", "--host", server.host,
                          "-i", "i", "-f", "f", str(csv_file)])
        assert rc == 0, err
        holder = server.holder
        assert holder.fragment("i", "f", "standard", 0).row(1).count() == 2
        assert holder.fragment("i", "f", "standard", 1).row(1).count() == 1
        assert holder.fragment("i", "f", "standard", 2).row(7).count() == 1

    def test_import_rejects_comment_lines(self, server, tmp_path):
        """np.loadtxt silently skips '#' lines; the import pipeline must
        not — the reference parser errors on them (ctl/import.go)."""
        setup_schema(server)
        csv_file = tmp_path / "c.csv"
        csv_file.write_text("1,2\n# not a bit\n3,4\n")
        rc, _, err = run(["import", "--host", server.host,
                          "-i", "i", "-f", "f", str(csv_file)])
        assert rc == 1
        assert "row 2" in err

    @pytest.mark.parametrize("line,what", [
        ("-1,2", "row id"),          # negative: u64 would wrap
        ("1.5,2", "row id"),         # float: loadtxt would truncate
        ("1,2 # note", "column id"),  # inline comment
        (f"{1 << 64},2", "row id"),  # past ParseUint range
    ])
    def test_import_rejects_non_uint_fields(self, server, tmp_path,
                                            line, what):
        """numpy's C parser is laxer than the reference's ParseUint —
        these must all be per-row errors, never wrapped/truncated bits."""
        setup_schema(server)
        csv_file = tmp_path / "bad.csv"
        csv_file.write_text(f"1,2\n{line}\n")
        rc, _, err = run(["import", "--host", server.host,
                          "-i", "i", "-f", "f", str(csv_file)])
        assert rc == 1
        assert f"invalid {what} on row 2" in err

    def test_import_bad_row(self, server, tmp_path):
        setup_schema(server)
        csv_file = tmp_path / "bad.csv"
        csv_file.write_text("notanint,3\n")
        rc, _, err = run(["import", "--host", server.host,
                          "-i", "i", "-f", "f", str(csv_file)])
        assert rc == 1
        assert "invalid row id" in err

    def test_sort(self, tmp_path):
        from pilosa_tpu import SLICE_WIDTH
        csv_file = tmp_path / "s.csv"
        csv_file.write_text(f"5,{SLICE_WIDTH + 1}\n1,7\n0,9\n")
        rc, out, _ = run(["sort", str(csv_file)])
        assert rc == 0
        # Slice 0 rows first (by pos), then slice 1.
        assert out.splitlines() == ["0,9", "1,7", f"5,{SLICE_WIDTH + 1}"]


class TestBackupRestore:
    def test_roundtrip(self, server, tmp_path):
        setup_schema(server)
        server.holder.frame("i", "f").import_bits([1, 2], [3, 4])
        tarball = tmp_path / "backup.tar"
        rc, _, err = run(["backup", "--host", server.host, "-i", "i",
                          "-f", "f", "-o", str(tarball)])
        assert rc == 0, err
        assert tarball.stat().st_size > 0

        # Wipe and restore.
        server.holder.index("i").delete_frame("f")
        setup_schema(server)
        rc, _, err = run(["restore", "--host", server.host, "-i", "i",
                          "-f", "f", str(tarball)])
        assert rc == 0, err
        frag = server.holder.fragment("i", "f", "standard", 0)
        assert frag.row(1).count() == 1
        assert frag.row(2).count() == 1


class TestOffline:
    def test_check_ok_and_corrupt(self, server, tmp_path):
        setup_schema(server)
        frag = server.holder.frame("i", "f")
        frag.set_bit("standard", 1, 2)
        path = server.holder.fragment("i", "f", "standard", 0).path
        rc, out, _ = run(["check", path])
        assert rc == 0
        assert "ok" in out

        bad = tmp_path / "bad"
        bad.write_bytes(b"\x00" * 100)
        rc, out, _ = run(["check", str(bad)])
        assert rc == 1

    def test_inspect(self, server):
        setup_schema(server)
        server.holder.frame("i", "f").set_bit("standard", 0, 5)
        frag = server.holder.fragment("i", "f", "standard", 0)
        # inspect reads the FILE: wait for the group-commit flusher to
        # put the op there (a direct holder write returns before it).
        frag.wal_barrier()
        path = frag.path
        rc, out, _ = run(["inspect", path])
        assert rc == 0
        assert "Containers: 1" in out
        assert "array" in out


class TestBenchConfig:
    def test_bench_set_bit(self, server):
        setup_schema(server)
        rc, out, err = run(["bench", "--host", server.host, "-i", "i",
                            "-f", "f", "--op", "set-bit", "-n", "10"])
        assert rc == 0, err
        assert "op/sec" in out

    def test_config_prints_toml(self):
        rc, out, _ = run(["config"])
        assert rc == 0
        assert 'host = "localhost:10101"' in out

    def test_config_load_priority(self, tmp_path, monkeypatch):
        from pilosa_tpu.utils import config as config_mod
        toml = tmp_path / "cfg.toml"
        toml.write_text('data-dir = "/tmp/x"\nhost = "h1:1"\n'
                        '[cluster]\nreplicas = 3\nhosts = ["h1:1","h2:2"]\n'
                        'polling-interval = "30s"\n'
                        '[anti-entropy]\ninterval = "5m"\n')
        cfg = config_mod.load(str(toml), env={})
        assert cfg.data_dir == "/tmp/x"
        assert cfg.cluster.replica_n == 3
        assert cfg.cluster.polling_interval == 30.0
        assert cfg.anti_entropy_interval == 300.0
        # env beats file
        cfg = config_mod.load(str(toml), env={"PILOSA_HOST": "h9:9"})
        assert cfg.host == "h9:9"

    def test_config_parse_plugins(self, tmp_path):
        """[plugins] path parses from TOML and env, and round-trips
        through `pilosa config` output (cmd/server_test.go:86,
        config.go:48-50)."""
        from pilosa_tpu.utils import config as config_mod
        toml = tmp_path / "cfg.toml"
        toml.write_text('[plugins]\npath = "/var/sloth"\n')
        cfg = config_mod.load(str(toml), env={})
        assert cfg.plugins_path == "/var/sloth"
        assert 'path = "/var/sloth"' in cfg.to_toml()
        cfg = config_mod.load(str(toml),
                              env={"PILOSA_PLUGINS_PATH": "/opt/p"})
        assert cfg.plugins_path == "/opt/p"
        # default prints the empty key, like ctl/config.go:58
        rc, out, _ = run(["config"])
        assert rc == 0 and "[plugins]" in out


def test_check_accepts_reference_format_golden_files(capsys):
    """`pilosa check` must validate files in the reference wire format
    (the golden interchange fixtures) — CLI × interchange composition."""
    import glob
    import os

    from pilosa_tpu.cli.commands import main as cli_main
    golden = sorted(glob.glob(os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "golden", "*.roaring")))
    assert golden
    rc = cli_main(["check", *golden])
    out = capsys.readouterr().out
    assert rc == 0
    assert out.count(": ok") == len(golden)

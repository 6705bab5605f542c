"""CLI dispatcher + offline commands + utils (glog/config/security).

Reference surfaces: weed/weed.go:38-80 (dispatch), weed/command/fix.go,
compact.go, export.go, scaffold.go, upload.go, download.go;
weed/util/config.go (TOML + WEED_ env); weed/security/jwt.go.
"""

from __future__ import annotations

import io
import json
import os
import tarfile

import pytest

from seaweedfs_tpu.command import main, parse_flags
from seaweedfs_tpu.core.needle import Needle
from seaweedfs_tpu.storage.volume import Volume
from seaweedfs_tpu.utils import security
from seaweedfs_tpu.utils.config import Configuration, load_configuration


# -- flag parsing ------------------------------------------------------------

def test_parse_flags_styles():
    flags, rest = parse_flags(["-port", "9333", "-dir=/d", "-quiet=true",
                               "file1", "file2"])
    assert flags.get_int("port") == 9333
    assert flags.get("dir") == "/d"
    assert flags.get_bool("quiet") is True
    assert rest == ["file1", "file2"]
    flags2, rest2 = parse_flags(["-force"])  # trailing bare boolean
    assert flags2.get_bool("force") is True and rest2 == []


def test_usage_and_unknown(capsys):
    assert main([]) == 0
    out = capsys.readouterr().out
    for name in ("master", "volume", "filer", "s3", "shell", "upload",
                 "download", "fix", "compact", "export", "scaffold",
                 "version", "server", "watch", "webdav"):
        assert name in out, f"command {name} not registered"
    assert main(["nonsense"]) == 2


def test_version(capsys):
    assert main(["version"]) == 0
    assert "version" in capsys.readouterr().out


def test_scaffold(capsys, tmp_path):
    assert main(["scaffold", "-config=security"]) == 0
    assert "[jwt.signing]" in capsys.readouterr().out
    assert main(["scaffold", "-config=filer",
                 f"-output={tmp_path}"]) == 0
    assert (tmp_path / "filer.toml").is_file()


# -- offline commands on a real volume --------------------------------------

@pytest.fixture
def volume_dir(tmp_path):
    vol = Volume(str(tmp_path), "", 7)
    for i in range(1, 21):
        n = Needle(id=i, cookie=0x1234, data=f"payload-{i}".encode())
        n.set_name(f"file-{i}.txt".encode())
        vol.write_needle(n)
    vol.delete_needle(3)
    vol.delete_needle(9)
    vol.close()
    return tmp_path


def test_fix_regenerates_idx(volume_dir, capsys):
    idx = volume_dir / "7.idx"
    original = idx.read_bytes()
    idx.unlink()
    assert main(["fix", f"-dir={volume_dir}", "-volumeId=7"]) == 0
    regenerated = idx.read_bytes()
    # Same live set: reload and compare the needle map contents.
    vol = Volume(str(volume_dir), "", 7)
    try:
        assert vol.file_count() == 18
        assert vol.read_needle(5).data == b"payload-5"
        with pytest.raises(Exception):
            vol.read_needle(3)
    finally:
        vol.close()
    assert len(regenerated) >= len(original) - 32


def test_compact_shrinks(volume_dir):
    before = (volume_dir / "7.dat").stat().st_size
    assert main(["compact", f"-dir={volume_dir}", "-volumeId=7"]) == 0
    after = (volume_dir / "7.dat").stat().st_size
    assert after < before
    vol = Volume(str(volume_dir), "", 7)
    try:
        assert vol.read_needle(5).data == b"payload-5"
        with pytest.raises(Exception):
            vol.read_needle(3)
    finally:
        vol.close()


def test_export_tar_and_listing(volume_dir, tmp_path, capsys):
    tar_path = tmp_path / "out.tar"
    assert main(["export", f"-dir={volume_dir}", "-volumeId=7",
                 f"-o={tar_path}"]) == 0
    with tarfile.open(tar_path) as tar:
        names = tar.getnames()
        assert "file-5.txt" in names and "file-3.txt" not in names
        data = tar.extractfile("file-5.txt").read()
        assert data == b"payload-5"
    # listing mode (no -o)
    assert main(["export", f"-dir={volume_dir}", "-volumeId=7"]) == 0
    out = capsys.readouterr().out
    assert "file-5.txt" in out and "file-9.txt" not in out


# -- config ------------------------------------------------------------------

def test_config_load_and_env_override(tmp_path, monkeypatch):
    (tmp_path / "security.toml").write_text(
        '[jwt.signing]\nkey = "abc"\nexpires_after_seconds = 10\n')
    cfg = load_configuration("security", search_paths=[str(tmp_path)])
    assert cfg.get_string("jwt.signing.key") == "abc"
    assert cfg.get_int("jwt.signing.expires_after_seconds") == 10
    monkeypatch.setenv("WEED_JWT_SIGNING_KEY", "override")
    assert cfg.get_string("jwt.signing.key") == "override"
    # missing optional config is empty, required raises
    assert load_configuration("nothere",
                              search_paths=[str(tmp_path)]).get("x") is None
    with pytest.raises(FileNotFoundError):
        load_configuration("nothere", required=True,
                           search_paths=[str(tmp_path)])


def test_config_sub_and_bool():
    cfg = Configuration({"sqlite": {"enabled": True, "file": "f.db"}})
    assert cfg.get_bool("sqlite.enabled") is True
    assert cfg.sub("sqlite") == {"enabled": True, "file": "f.db"}


# -- security / jwt ----------------------------------------------------------

def test_jwt_round_trip():
    tok = security.gen_jwt("secret", 60, "3,0144b2c8f1")
    claims = security.decode_jwt("secret", tok)
    assert claims["fid"] == "3,0144b2c8f1"


def test_jwt_bad_signature_and_expiry():
    tok = security.gen_jwt("secret", 60, "3,ab")
    with pytest.raises(security.JwtError):
        security.decode_jwt("wrong", tok)
    expired = security.gen_jwt("secret", -100, "3,ab")
    with pytest.raises(security.JwtError):
        security.decode_jwt("secret", expired)


def test_guard():
    g = security.Guard(signing_key="k", expires_seconds=60)
    assert g.is_active
    tok = security.gen_jwt("k", 60, "3,ab")
    g.check_jwt(tok, "3,ab")
    g.check_jwt(tok, "3,ab_1")  # chunk-suffix variants allowed
    with pytest.raises(security.JwtError):
        g.check_jwt(tok, "4,cd")
    with pytest.raises(security.JwtError):
        g.check_jwt("", "3,ab")
    inactive = security.Guard()
    assert not inactive.is_active
    inactive.check_jwt("", "3,ab")  # no-op when no key configured


def test_environment_names_read_by_the_package_are_the_documented_ones():
    """README's "Environment variables" table is the one list of the
    `SEAWEEDFS_TPU_*` names: every name the package's source holds is a
    row of it, and it has no row the source does not read."""
    import re
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    name = re.compile(r"SEAWEEDFS_TPU_[A-Z_]+")
    read = set()
    for here, _dirs, files in os.walk(os.path.join(root, "seaweedfs_tpu")):
        for f in files:
            if f.endswith(".py"):
                with open(os.path.join(here, f), encoding="utf-8") as src:
                    read |= set(name.findall(src.read()))
    with open(os.path.join(root, "README.md"), encoding="utf-8") as f:
        readme = f.read()
    section = readme.split("## Environment variables\n", 1)[1]
    section = section.split("\n## ", 1)[0]
    rows = [line.split("|")[1] for line in section.splitlines()
            if line.startswith("| `SEAWEEDFS_TPU_")]
    documented = {name.search(cell).group(0) for cell in rows}
    assert len(rows) == len(documented), "a name has two rows"
    assert documented == read, (sorted(read - documented),
                                sorted(documented - read))
    # one table: a name explained elsewhere in the README is in it too
    assert set(name.findall(readme)) <= documented


def test_glog(capsys):
    from seaweedfs_tpu.utils import glog
    glog.setup(verbosity=1)
    glog.infof("hello %s", "world")
    glog.v(1).infof("visible")
    glog.v(5).infof("hidden")
    err = capsys.readouterr().err
    assert "hello world" in err and "visible" in err
    assert "hidden" not in err


# -- end-to-end: `weed server` subprocess + upload/download ------------------

def test_server_upload_download_roundtrip(tmp_path, capsys):
    import socket
    import subprocess
    import sys as _sys
    import time as _time
    import urllib.request

    def free_port():
        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            return s.getsockname()[1]

    mport, vport = free_port(), free_port()
    data_dir = tmp_path / "data"
    data_dir.mkdir()
    proc = subprocess.Popen(
        [_sys.executable, "-m", "seaweedfs_tpu", "server",
         f"-master.port={mport}", f"-volume.port={vport}",
         f"-dir={data_dir}", f"-mdir={tmp_path}"],
        stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL)
    try:
        deadline = _time.time() + 20
        while True:  # wait until the volume server has registered
            try:
                with urllib.request.urlopen(
                        f"http://127.0.0.1:{mport}/dir/status",
                        timeout=1) as resp:
                    status = json.loads(resp.read())
                if status.get("topology", {}).get("children"):
                    break  # a data node has registered
            except Exception:
                pass
            if _time.time() > deadline:
                raise TimeoutError("cluster did not come up")
            _time.sleep(0.2)
        src = tmp_path / "hello.txt"
        src.write_bytes(b"hello from the cli")
        assert main(["upload", f"-master=127.0.0.1:{mport}",
                     str(src)]) == 0
        fid = json.loads(capsys.readouterr().out)[0]["fid"]
        out_dir = tmp_path / "dl"
        assert main(["download", f"-server=127.0.0.1:{mport}",
                     f"-dir={out_dir}", fid]) == 0
        name = fid.replace(",", "_")
        assert (out_dir / name).read_bytes() == b"hello from the cli"
    finally:
        proc.terminate()
        proc.wait(timeout=10)


@pytest.mark.parametrize("transport", ["json", "grpc"])
def test_server_full_stack_s3_webdav(tmp_path, transport):
    """Capstone: one `weed server -filer=true -s3=true -webdav=true`
    process; an object PUT through the S3 gateway reads back through
    S3, the filer HTTP API, and WebDAV.

    Parametrized over the filer's internal master transport: with
    WEED_INTERNAL_GRPC=1 the filer's assign/lookup traffic rides the
    wire-compatible master_pb.Seaweed gRPC plane instead of the JSON
    plane, so the gRPC facade is exercised by real cluster operation,
    not only its dedicated tests (round-4 facade-drift canary)."""
    import os as _os
    import socket
    import subprocess
    import sys as _sys
    import time as _time
    import urllib.request

    def free_port():
        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            return s.getsockname()[1]

    mport, vport, fport, s3port, davport = (free_port() for _ in range(5))
    data_dir = tmp_path / "data"
    data_dir.mkdir()
    env = dict(_os.environ)
    if transport == "grpc":
        env["WEED_INTERNAL_GRPC"] = "1"
    proc = subprocess.Popen(
        [_sys.executable, "-m", "seaweedfs_tpu", "server",
         f"-master.port={mport}", f"-volume.port={vport}",
         f"-dir={data_dir}", f"-mdir={tmp_path}",
         "-filer=true", f"-filer.port={fport}",
         "-s3=true", f"-s3.port={s3port}",
         "-webdav=true", f"-webdav.port={davport}"],
        env=env, stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL)

    def wait_http(url, deadline):
        while _time.time() < deadline:
            try:
                urllib.request.urlopen(url, timeout=1)
                return
            except urllib.error.HTTPError:
                return  # server answered (any status)
            except Exception:
                _time.sleep(0.2)
        raise TimeoutError(url)

    try:
        deadline = _time.time() + 30
        for port, path in ((mport, "/dir/status"), (fport, "/"),
                           (s3port, "/"), (davport, "/")):
            wait_http(f"http://127.0.0.1:{port}{path}", deadline)
        # wait for the volume server registration
        while _time.time() < deadline:
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{mport}/dir/status",
                    timeout=2) as resp:
                if json.loads(resp.read()).get(
                        "topology", {}).get("children"):
                    break
            _time.sleep(0.2)
        s3 = f"http://127.0.0.1:{s3port}"
        body = b"through the S3 gateway" * 10
        # create bucket + put object (anonymous mode: no identities)
        urllib.request.urlopen(urllib.request.Request(
            f"{s3}/caps", method="PUT"), timeout=10)
        urllib.request.urlopen(urllib.request.Request(
            f"{s3}/caps/dir/obj.txt", data=body, method="PUT"),
            timeout=10)
        # read back through S3
        with urllib.request.urlopen(f"{s3}/caps/dir/obj.txt",
                                    timeout=10) as resp:
            assert resp.read() == body
        # the same object through the filer namespace
        with urllib.request.urlopen(
                f"http://127.0.0.1:{fport}/buckets/caps/dir/obj.txt",
                timeout=10) as resp:
            assert resp.read() == body
        # and through WebDAV
        with urllib.request.urlopen(
                f"http://127.0.0.1:{davport}/buckets/caps/dir/obj.txt",
                timeout=10) as resp:
            assert resp.read() == body
        # S3 list sees it
        with urllib.request.urlopen(
                f"{s3}/caps?list-type=2&prefix=dir/",
                timeout=10) as resp:
            listing = resp.read()
        assert b"dir/obj.txt" in listing
    finally:
        proc.terminate()
        proc.wait(timeout=10)

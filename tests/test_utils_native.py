"""Hostlist grammar (native C++ vs pure Python parity), native resource
algebra vs the JAX ops, config loading, and the daemon entry points.

Reference counterparts: String.h:88-105 (ParseHostList /
HostNameListToStr), PublicHeader.h:760-778 (resource algebra),
etc/config.yaml → Ctld::Config."""

import ctypes
import os
import subprocess
import sys
import time

import numpy as np
import pytest

from cranesched_tpu.utils import hostlist, native
from cranesched_tpu.utils.config import load_config, parse_max_age, parse_mem

CASES = [
    ("cn1", ["cn1"]),
    ("cn[1-3]", ["cn1", "cn2", "cn3"]),
    ("cn[01-03]", ["cn01", "cn02", "cn03"]),
    ("cn[1-2,5]", ["cn1", "cn2", "cn5"]),
    ("cn[1-2]x", ["cn1x", "cn2x"]),
    ("a1,b[2-3],c", ["a1", "b2", "b3", "c"]),
    ("gpu[08-10]", ["gpu08", "gpu09", "gpu10"]),
]


def test_native_library_builds_and_loads():
    assert native.available(), "native library must build (g++ is baked)"


def test_native_library_is_keyed_by_source_and_cpu(monkeypatch):
    """The build uses -march=native: a .so that came with a copy of the
    tree from another CPU must never be loaded, so the file name carries
    a key of the source text and the build host's CPU."""
    assert native.available()
    here = native._so_path()
    assert os.path.exists(here)
    assert os.path.basename(here).startswith("libcrane_native-")
    monkeypatch.setattr(native, "_host_cpu", lambda: "another cpu")
    assert native._so_path() != here


@pytest.mark.parametrize("expr,expected", CASES)
def test_parse_native_and_python_agree(expr, expected):
    assert native.parse_hostlist(expr) == expected
    assert hostlist._parse_py(expr) == expected


def test_compress_roundtrip_native_and_python():
    for expr, names in CASES:
        native_c = native.compress_hostlist(names)
        py_c = hostlist._compress_py(names)
        assert native_c == py_c
        # compression must round-trip through parse
        assert hostlist.parse_hostlist(native_c) == names


def test_compress_merges_ranges():
    names = [f"cn{i}" for i in range(1, 11)] + ["cn20", "other"]
    assert hostlist.compress_hostlist(names) == "cn[1-10,20],other"


def test_parse_malformed_raises():
    for bad in ("cn[", "cn[]", "cn[3-1]", "cn[a-b]"):
        with pytest.raises(ValueError):
            native.parse_hostlist(bad)
        with pytest.raises(ValueError):
            hostlist._parse_py(bad)


def test_native_resource_algebra_matches_jax_ops():
    import jax.numpy as jnp
    from cranesched_tpu.ops.resources import fit_count, fits
    lib = native.load()
    rng = np.random.default_rng(0)
    for _ in range(50):
        dims = int(rng.integers(1, 9))
        req = rng.integers(0, 100, dims).astype(np.int32)
        avail = rng.integers(0, 100, dims).astype(np.int32)
        want_fits = bool(fits(jnp.asarray(req), jnp.asarray(avail)))
        got = lib.crane_fits(
            req.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
            avail.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)), dims)
        assert bool(got) == want_fits
        want_count = int(fit_count(jnp.asarray(avail), jnp.asarray(req)))
        got_count = lib.crane_fit_count(
            avail.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
            req.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)), dims)
        assert got_count == want_count


def test_native_fits_batch():
    lib = native.load()
    rng = np.random.default_rng(1)
    avail = rng.integers(0, 50, (64, 4)).astype(np.int32)
    req = rng.integers(0, 50, 4).astype(np.int32)
    out = np.zeros(64, np.uint8)
    lib.crane_fits_batch(
        req.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        avail.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        64, 4, out.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)))
    want = np.all(req[None, :] <= avail, axis=1)
    np.testing.assert_array_equal(out.astype(bool), want)


# ---------------- config ----------------

def test_parse_mem_and_max_age():
    assert parse_mem("64G") == 64 << 30
    assert parse_mem("512M") == 512 << 20
    assert parse_mem(1024) == 1024
    assert parse_max_age("14-0") == 14 * 86400
    assert parse_max_age("1:30:00") == 5400
    assert parse_max_age("90") == 5400   # bare minutes


def test_load_example_config_and_build():
    cfg = load_config("etc/config.yaml")
    assert cfg.cluster_name == "demo"
    meta, sched = cfg.build()
    assert len(meta.nodes) == 6           # cn[01-04] + gpu[1-2]
    assert meta.node_by_name("cn01").partitions == {"cpu"}
    assert meta.partitions["gpu"].priority == 200
    assert sched.config.priority_weights.max_age == 14 * 86400
    assert sched.config.backfill


@pytest.mark.parametrize("key,value", [
    ("Incremental", "false"), ("ResidentState", "false"),
    ("MaxStreams", "2"), ("BlockJobs", "128")])
def test_removed_scheduler_key_is_refused_by_name(tmp_path, key, value):
    """A site's stale key must not silently change meaning: the file is
    refused, and the message says which key to take out."""
    cfg = tmp_path / "config.yaml"
    cfg.write_text(f"""
ClusterName: t
Partitions: [{{name: default}}]
Scheduler:
  Backfill: true
  {key}: {value}
""")
    with pytest.raises(ValueError, match=rf"Scheduler: {key}\b"):
        load_config(str(cfg))
    # the same file without the key loads
    cfg.write_text(cfg.read_text().replace(f"  {key}: {value}\n", ""))
    assert load_config(str(cfg)).scheduler == {"Backfill": True}


# ---------------- daemon entry points ----------------

def test_ctld_main_and_craned_main_end_to_end(tmp_path):
    cfg = tmp_path / "config.yaml"
    cfg.write_text(f"""
ClusterName: t
Listen: 127.0.0.1:0
Wal: {tmp_path}/ctld.wal
Partitions: [{{name: default}}]
""")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH="/root/repo")
    ctld = subprocess.Popen(
        [sys.executable, "-m", "cranesched_tpu.ctld_main", "-c",
         str(cfg), "--cycle-interval", "0.2"],
        stdout=subprocess.PIPE, text=True, env=env, cwd="/root/repo")
    try:
        line = ctld.stdout.readline()
        port = int(line.split("port")[1].split()[0])
        craned = subprocess.Popen(
            [sys.executable, "-m", "cranesched_tpu.craned_main",
             "--name", "mn0", "--ctld", f"127.0.0.1:{port}",
             "--cpu", "4", "--memory", "4G", "--workdir", str(tmp_path),
             "--ping-interval", "0.5",
             "--cgroup-root", str(tmp_path / "nocg")],
            stdout=subprocess.PIPE, text=True, env=env,
            cwd="/root/repo")
        try:
            # wait for the craned to register before submitting
            deadline = time.time() + 15
            while time.time() < deadline:
                q = subprocess.run(
                    [sys.executable, "-m", "cranesched_tpu.cli",
                     "--server", f"127.0.0.1:{port}", "cinfo"],
                    capture_output=True, text=True, env=env,
                    cwd="/root/repo")
                if "mn0" in q.stdout:
                    break
                time.sleep(0.3)
            assert "mn0" in q.stdout, f"craned never registered:\n{q.stdout}"
            r = subprocess.run(
                [sys.executable, "-m", "cranesched_tpu.cli",
                 "--server", f"127.0.0.1:{port}", "cbatch",
                 "--cpu", "1"],
                capture_output=True, text=True, env=env,
                cwd="/root/repo")
            assert "Submitted batch job 1" in r.stdout
            # job 2 writes a real file through the full daemon stack
            r = subprocess.run(
                [sys.executable, "-m", "cranesched_tpu.cli",
                 "--server", f"127.0.0.1:{port}", "cbatch",
                 "--cpu", "1"],
                capture_output=True, text=True, env=env,
                cwd="/root/repo")
            deadline = time.time() + 20
            done = False
            while time.time() < deadline:
                q = subprocess.run(
                    [sys.executable, "-m", "cranesched_tpu.cli",
                     "--server", f"127.0.0.1:{port}", "cacct"],
                    capture_output=True, text=True, env=env,
                    cwd="/root/repo")
                if q.stdout.count("Completed") >= 2:
                    done = True
                    break
                time.sleep(0.5)
            assert done, f"jobs never completed; last cacct:\n{q.stdout}"
        finally:
            craned.terminate()
            craned.wait(timeout=10)
    finally:
        ctld.terminate()
        ctld.wait(timeout=10)

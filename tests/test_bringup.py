"""Bring-up contract: a process says which backend it runs on and fails
when it cannot have the one it was asked for; the compile cache sits at a
path that can be placed from outside; a native library that does not load
is reported; and chip_smoke.py refuses to pass anywhere but on a TPU.

Everything that has to start a fresh jax runs in a subprocess — this
session's jax is already initialised on the CPU test mesh.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(args, env_extra=None, drop=(), timeout=300):
    env = {k: v for k, v in os.environ.items() if k not in drop}
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env.update(env_extra or {})
    # the conftest's 8 virtual devices are this session's business
    env.pop("XLA_FLAGS", None)
    return subprocess.run([sys.executable, *args], env=env, cwd=REPO,
                          capture_output=True, text=True, timeout=timeout)


def test_server_exits_nonzero_when_platform_cannot_initialise(tmp_path):
    cfg = tmp_path / "s.toml"
    cfg.write_text(f'[data]\ndir = "{tmp_path / "d"}"\n'
                   '[http]\nbind-address = "127.0.0.1:0"\n')
    r = _run(["-m", "opengemini_tpu.server.app", "-config", str(cfg)],
             {"JAX_PLATFORMS": "nonesuch"}, timeout=120)
    assert r.returncode != 0
    assert "nonesuch" in r.stderr          # says which platform, and why
    assert "listening" not in r.stdout     # never served anywhere else
    assert "serving on CPU" not in r.stdout + r.stderr


_CACHE_SNIPPET = (
    "from opengemini_tpu.utils import backend; import jax, json;"
    "print(json.dumps([backend.configure_compile_cache(),"
    " jax.config.jax_compilation_cache_dir,"
    " jax.config.jax_persistent_cache_min_compile_time_secs]))")


def test_compile_cache_is_the_fixed_checkout_path_in_every_process():
    got = []
    for _ in range(2):
        r = _run(["-c", _CACHE_SNIPPET], drop=("JAX_COMPILATION_CACHE_DIR",))
        assert r.returncode == 0, r.stderr[-2000:]
        got.append(json.loads(r.stdout.strip().splitlines()[-1]))
    want = os.path.join(REPO, ".jax_cache")
    assert got[0] == got[1] == [want, want, 0.0]


def test_compile_cache_env_is_left_alone(tmp_path):
    placed = str(tmp_path / "placed-cache")
    r = _run(["-c", _CACHE_SNIPPET], {"JAX_COMPILATION_CACHE_DIR": placed})
    assert r.returncode == 0, r.stderr[-2000:]
    # JAX read the variable itself; the helper set no other directory
    assert json.loads(r.stdout.strip().splitlines()[-1]) == [
        placed, placed, 0.0]


def test_native_library_that_does_not_load_is_reported(tmp_path, monkeypatch):
    from opengemini_tpu import native

    monkeypatch.setattr(native, "NATIVE_DIR", str(tmp_path))
    monkeypatch.setattr(native, "_status", {})
    # a file that is there but is no shared object
    (tmp_path / "libogtcodecs.so").write_bytes(b"not an ELF file")
    assert native.open_library("codecs", lambda lib: None) is None
    assert "dlopen" in native._status["codecs"]
    # no file and nothing to build it from: make's own words are kept
    assert native.open_library("textindex", lambda lib: None) is None
    assert native._status["textindex"].startswith("make")


def test_native_library_is_built_when_missing(tmp_path, monkeypatch):
    import shutil

    from opengemini_tpu import native

    for name in ("Makefile", "codecs.cpp"):
        shutil.copy(os.path.join(native.NATIVE_DIR, name), tmp_path / name)
    monkeypatch.setattr(native, "NATIVE_DIR", str(tmp_path))
    monkeypatch.setattr(native, "_status", {})
    lib = native.open_library("codecs", native._bind)
    assert lib is not None and native._status["codecs"] == ""
    assert (tmp_path / "libogtcodecs.so").exists()


def test_pallas_probe_failure_is_an_error_on_tpu(monkeypatch):
    """On the CPU a failing probe is the tests' skip reason; anywhere
    else it must raise instead of reading as 'unsupported'."""
    import jax
    from jax.experimental import pallas as pl

    from opengemini_tpu.utils import devobs

    def refuse(*a, **k):
        raise NotImplementedError("Mosaic refused")

    monkeypatch.setattr(pl, "pallas_call", refuse)
    ok, why = devobs._probe_pallas()
    assert not ok and "Mosaic refused" in why
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(NotImplementedError):
        devobs._probe_pallas()


def test_chip_smoke_cpu_dry_run_passes():
    r = _run(["chip_smoke.py", "--cpu-dry-run"], timeout=600)
    assert r.returncode == 0, (r.stdout[-3000:], r.stderr[-3000:])
    assert "CPU DRY RUN" in r.stdout
    last = json.loads(r.stdout.strip().splitlines()[-1])
    assert last["ok"] and last["cpu_dry_run"]
    assert last["device"]["platform"] == "cpu"


def test_chip_smoke_refuses_the_cpu_without_the_flag():
    r = _run(["chip_smoke.py"], {"JAX_PLATFORMS": "cpu"}, timeout=600)
    assert r.returncode != 0
    assert "platform is 'cpu'" in r.stderr
    # no result line: the last thing on stdout is not a JSON verdict
    assert '"ok"' not in r.stdout

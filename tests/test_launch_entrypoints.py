"""The launch entry points driven in-process, as `chip_smoke.py` drives
them on the chip, at reduced sizes on the CPU: train from the store,
bitwise restore, resume, serve, and the four-device elastic reshard."""

from __future__ import annotations

import importlib.util
import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import pytest

from repro.io.stores import clear_store_cache
from repro.launch import compile_cache

ROOT = Path(__file__).resolve().parent.parent


def _load_chip_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


smoke = _load_chip_smoke()


@pytest.fixture
def isolated(monkeypatch, tmp_path):
    """Fresh store instances, and no compile cache written into the
    checkout: with the variable set, the entry points set no cache dir."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jax-cache"))
    cache_dir = jax.config.jax_compilation_cache_dir
    clear_store_cache()
    try:
        yield
    finally:
        clear_store_cache()
        jax.config.update("jax_compilation_cache_dir", cache_dir)


def _phases(out: str) -> dict[str, dict]:
    lines = [json.loads(ln) for ln in out.splitlines() if ln.startswith('{"phase"')]
    return {ln.pop("phase"): ln for ln in lines}


def test_train_restore_resume_serve(isolated, capsys):
    smoke.run_one_chip(["--reduced", "--batch", "2", "--seq-len", "64"],
                       ["--reduced", "--batch", "2", "--prompt-len", "16", "--gen", "4"])
    phases = _phases(capsys.readouterr().out)
    assert list(phases) == ["link", "train", "restore", "resume", "serve"]
    assert sorted(int(s) for s in phases["train"]["losses"]) == [5, 10, 15, 20]
    assert phases["restore"]["step"] == smoke.TRAIN_STEPS
    assert phases["restore"]["bitwise_equal"] is True
    assert phases["resume"]["resumed_from"] == smoke.TRAIN_STEPS
    assert sorted(int(s) for s in phases["resume"]["losses"]) == [25, 30]
    assert phases["resume"]["restore_s"] > 0
    assert (phases["serve"]["batch"], phases["serve"]["gen"]) == (2, 4)


def test_train_saves_each_step_once(isolated, monkeypatch):
    from repro.ckpt import manager
    from repro.launch import train

    saved = []
    real_save = manager.save_checkpoint

    def recording_save(store, prefix, step, *args, **kwargs):
        saved.append(step)
        return real_save(store, prefix, step, *args, **kwargs)

    monkeypatch.setattr(manager, "save_checkpoint", recording_save)
    argv = ["--reduced", "--batch", "2", "--seq-len", "32", "--ckpt-interval", "2",
            "--ckpt-store", "sims3://saves-once?latency_ms=0"]
    train.main([*argv, "--steps", "4"])
    assert saved == [2, 4]          # the loop's save at 4 is the final one
    run = train.main([*argv, "--steps", "5"])
    assert run.start_step == 4 and set(run.losses) == {5}
    assert saved == [2, 4, 5]       # 5 is off the interval: forced


def test_chip_smoke_refuses_a_host_without_a_tpu(isolated, capsys):
    assert smoke.main([]) == 1
    out, err = capsys.readouterr()
    assert out == ""
    assert "needs a TPU" in err and repr(jax.devices()[0].platform) in err


def test_elastic_phase_needs_four_devices(isolated):
    with pytest.raises(RuntimeError, match="needs 4 devices"):
        smoke.run_elastic(reduced=True)


def _child_env(**extra) -> dict:
    env = {k: v for k, v in os.environ.items() if k not in (
        "JAX_COMPILATION_CACHE_DIR", "XLA_FLAGS")}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=str(ROOT / "src"), **extra)
    return env


def test_elastic_phase_on_four_cpu_devices():
    """The four-chip path on four virtual CPU devices: mesh, shardings,
    resharded restore and the checks themselves."""
    code = "import chip_smoke; chip_smoke.run_elastic(reduced=True)"
    proc = subprocess.run(
        [sys.executable, "-c", code], cwd=ROOT, capture_output=True, text=True,
        timeout=300,
        env=_child_env(XLA_FLAGS="--xla_force_host_platform_device_count=4"))
    assert proc.returncode == 0, proc.stderr[-4000:]
    elastic = _phases(proc.stdout)["elastic"]
    assert elastic["bitwise_equal"] is True
    assert 0 < elastic["sharded_leaves"] <= elastic["leaves"]


_COMPILE_ONE = """
import sys
from pathlib import Path
import jax, jax.numpy as jnp
from repro.launch import compile_cache
if len(sys.argv) > 1:
    compile_cache.CACHE_DIR = Path(sys.argv[1])
print(compile_cache.use_compile_cache())
jax.jit(lambda x: x * 3 + 1)(jnp.ones(7)).block_until_ready()
"""


def _compile_in_child(tmp_path, *argv, **env) -> str:
    proc = subprocess.run(
        [sys.executable, "-c", _COMPILE_ONE, *argv], cwd=tmp_path,
        capture_output=True, text=True, timeout=120,
        env=_child_env(JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0", **env))
    assert proc.returncode == 0, proc.stderr[-4000:]
    return proc.stdout.strip()


def test_compile_cache_lands_only_in_the_env_dir(tmp_path):
    env_dir, fixed = tmp_path / "from-env", tmp_path / "fixed"
    used = _compile_in_child(tmp_path, str(fixed),
                             JAX_COMPILATION_CACHE_DIR=str(env_dir))
    assert used == str(env_dir)
    assert any(env_dir.iterdir())
    assert not fixed.exists()


def test_compile_cache_defaults_to_the_fixed_dir(tmp_path):
    fixed = tmp_path / "fixed"
    assert _compile_in_child(tmp_path, str(fixed)) == str(fixed)
    assert any(fixed.iterdir())


def test_default_compile_cache_dir_is_in_the_checkout_and_ignored():
    assert compile_cache.CACHE_DIR == ROOT / ".jax_cache"
    ignored = (ROOT / ".gitignore").read_text().split()
    assert ".jax_cache/" in ignored

"""Platform plumbing: kernel backend by platform, kernel fallbacks, the
compilation-cache helper, the shared training path and ``chip_smoke.py``'s
device check."""

from __future__ import annotations

import os
import shutil
import signal
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import kernels as K
from repro.checkpoint import store
from repro.configs.registry import get_smoke_config
from repro.launch import cache
from repro.launch.train import CPU_SHAPES, build_parser, train

REPO = Path(__file__).resolve().parent.parent


# -- kernel backend ------------------------------------------------------------


@pytest.mark.parametrize("platform,backend", [("tpu", "pallas"), ("cpu", "ref"),
                                              ("gpu", "ref")])
def test_backend_follows_platform(monkeypatch, platform, backend):
    monkeypatch.setattr(K, "_override", None)
    monkeypatch.setattr(jax, "default_backend", lambda: platform)
    assert K.get_backend() == backend


def test_set_backend_overrides_platform(monkeypatch):
    monkeypatch.setattr(K, "_override", None)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    K.set_backend("ref")
    assert K.get_backend() == "ref"
    with pytest.raises(ValueError):
        K.set_backend("cuda")


def _untileable_calls():
    """One call per Wan-path kernel family at a shape it cannot tile."""
    x = jnp.ones((2, 8, 96), jnp.float32)  # D % 128 != 0
    mod = jnp.zeros((2, 96), jnp.float32)
    q = jnp.ones((1, 8, 2, 64), jnp.float32)  # head_dim 64
    return {
        "adaln": lambda: K.adaln_modulate(x, mod, mod),
        "rms": lambda: K.rms_norm(x, jnp.ones((96,), jnp.float32)),
        "qk_norm": lambda: K.qk_norm(x, x, jnp.ones(96), jnp.ones(96)),
        "flash": lambda: K.attention(q, q, q, causal=False),
    }


@pytest.mark.parametrize("kernel", sorted(_untileable_calls()))
def test_compiled_backend_refuses_fallback(monkeypatch, kernel):
    """On the chip, an untileable shape is an error, never a jnp stand-in."""
    monkeypatch.setattr(K, "_override", "pallas")
    with pytest.raises(NotImplementedError, match="set_backend"):
        _untileable_calls()[kernel]()


@pytest.mark.parametrize("kernel", sorted(_untileable_calls()))
def test_interpret_backend_warns_on_fallback(monkeypatch, kernel):
    monkeypatch.setattr(K, "_override", "pallas_interpret")
    with pytest.warns(K.KernelFallbackWarning):
        out = _untileable_calls()[kernel]()
    assert all(np.isfinite(np.asarray(o)).all() for o in jax.tree.leaves(out))


# -- compilation cache ---------------------------------------------------------


@pytest.fixture
def restore_cache_dir():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_cache_dir_from_env_is_left_to_jax(monkeypatch, restore_cache_dir, tmp_path):
    monkeypatch.setenv(cache.ENV_VAR, str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert cache.enable_compilation_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_cache_dir_defaults_to_fixed_checkout_path(monkeypatch, restore_cache_dir):
    monkeypatch.delenv(cache.ENV_VAR, raising=False)
    path = cache.enable_compilation_cache()
    assert path == str(REPO / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path
    assert cache.enable_compilation_cache() == path  # same path every time


def test_compiled_programs_land_in_env_cache_dir(tmp_path):
    """A fresh process with the variable set writes its programs there."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path),
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
               PYTHONPATH=str(REPO / "src"))
    code = (
        "import jax, jax.numpy as jnp\n"
        "from repro.launch.cache import enable_compilation_cache\n"
        "enable_compilation_cache()\n"
        "jax.jit(lambda x: jnp.sin(x) * 3)(jnp.ones(8)).block_until_ready()\n"
    )
    subprocess.run([sys.executable, "-c", code], env=env, check=True,
                   timeout=120, capture_output=True)
    assert any(tmp_path.iterdir())


# -- the shared training path ----------------------------------------------------


def test_train_path_runs_loader_trainer_checkpoint(tmp_path):
    """The launcher's train() (what chip_smoke.py drives): bucketed loader
    -> Trainer -> checkpoint, on the smoke MMDiT."""
    args = build_parser().parse_args([
        "--arch", "wan2.1-1.3b", "--smoke", "--adaptive", "--steps", "2",
        "--ckpt-dir", str(tmp_path), "--ckpt-every", "100",
    ])
    sigterm = signal.getsignal(signal.SIGTERM)  # train() routes it to preemption
    try:
        state, hist = train(args, get_smoke_config("wan2.1-1.3b"), CPU_SHAPES)
    finally:
        signal.signal(signal.SIGTERM, sigterm)
    assert len(hist.losses) == 2 and all(np.isfinite(hist.losses))
    assert int(jax.device_get(state["step"])) == 2
    assert store.latest_step(tmp_path) == 2


# -- chip_smoke.py ---------------------------------------------------------------


def _run_smoke(cwd: Path) -> subprocess.CompletedProcess:
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, "chip_smoke.py"], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=120)


def test_chip_smoke_refuses_cpu():
    proc = _run_smoke(REPO)
    assert proc.returncode != 0
    assert "no TPU found" in proc.stderr
    assert '"ok"' not in proc.stdout


def test_chip_smoke_alone_fails(tmp_path):
    """Without the rest of the repo the script cannot pass."""
    shutil.copy(REPO / "chip_smoke.py", tmp_path / "chip_smoke.py")
    proc = _run_smoke(tmp_path)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout

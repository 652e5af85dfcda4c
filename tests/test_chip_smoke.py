"""``chip_smoke.py`` and the serving builder it shares with
``repro.launch.serve``, exercised on the CPU: the smoke refuses to run
without a TPU or outside a checkout, and its served-path and reference
phases pass at a reduced size (Pallas kernels in interpret mode)."""

import importlib.util
import os
import shutil
import subprocess
import sys
from pathlib import Path

import jax
import pytest

ROOT = Path(__file__).resolve().parents[1]


def _load_smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", ROOT / "chip_smoke.py"
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture
def restore_cache_dir():
    prev = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", prev)


def test_smoke_refuses_cpu(capsys, restore_cache_dir):
    """No accelerator: exit non-zero, and no result line."""
    assert jax.devices()[0].platform == "cpu"
    assert _load_smoke().main([]) == 1
    out = capsys.readouterr().out
    assert "platform=cpu" in out and '"ok"' not in out


def test_smoke_refuses_outside_checkout(tmp_path):
    """Alone in a directory, the script finds no repository: it exits
    non-zero before importing JAX and prints no result."""
    shutil.copy(ROOT / "chip_smoke.py", tmp_path / "chip_smoke.py")
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    proc = subprocess.run(
        [sys.executable, str(tmp_path / "chip_smoke.py")],
        capture_output=True, text=True, cwd=tmp_path, env=env, timeout=120,
    )
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout


def test_compile_cache_dir(monkeypatch, restore_cache_dir):
    """The environment's directory wins and no other is set; without it
    the cache goes to a fixed directory at the repository root."""
    from repro.launch.serve import configure_compile_cache

    jax.config.update("jax_compilation_cache_dir", None)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
    assert configure_compile_cache() == "/elsewhere/cache"
    assert jax.config.jax_compilation_cache_dir is None
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    path = configure_compile_cache()
    assert path == str(ROOT / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path
    ignored = (ROOT / ".gitignore").read_text().split()
    assert ".jax_cache/" in ignored


@pytest.mark.slow
def test_smoke_phases_at_reduced_size(restore_cache_dir):
    """The smoke's served path (two nodes, a shared system prompt, a roam,
    page shipping) and its Pallas-vs-reference check, on the paper's
    config cut to CPU size — through the same builder the CLI uses."""
    from repro.configs import get_config
    from repro.serving import BatchedLLMService

    smoke = _load_smoke()
    cfg = get_config("paper-qwen1.5-0.5b").reduced()
    cluster, model, responses = smoke.served_path(cfg)
    services = [n.service for n in cluster.nodes.values()]
    assert len({id(s) for s in services}) == 2
    for svc in services:
        assert isinstance(svc, BatchedLLMService)
        assert svc.server.paged and svc.server.cfg.attn_impl == "pallas"
        assert svc.server.allocator.pools[0]["k"].devices() == {jax.devices()[0]}
    assert sum(len(rs) for rs in responses.values()) == 13
    srv = cluster.node("edge-0").service.server
    *checks, fault = smoke.reference_check(srv.cfg, srv.params, srv.device)
    for name, d, scale in checks:
        # float32 at this size: the two attention paths agree to rounding
        assert d <= 1e-4 * scale, (name, d, scale)
    assert fault[1] > smoke.TOL_REL * fault[2], fault

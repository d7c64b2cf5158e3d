"""Bring-up rules that hold on the CPU: the chip check refuses to run
without a TPU, a mesh never shrinks to what happens to be visible, the
compile cache follows JAX_COMPILATION_CACHE_DIR, the phases of
chip_smoke.py pass at a tiny width, and the dense oracle's TPU-shaped
gate path agrees with its reference path."""
import json
import os
import shutil
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.dense_engine import _apply_tiled, apply_matrix
from repro.distributed.lanes import make_lane_mesh, sim_devices
from repro.launch.cache import CHECKOUT_CACHE_DIR, use_compile_cache

ROOT = os.path.join(os.path.dirname(__file__), "..")
sys.path.insert(0, ROOT)
import chip_smoke  # noqa: E402


def _run(args, cwd, extra_env=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(extra_env or {}))
    return subprocess.run([sys.executable, *args], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def test_chip_smoke_refuses_without_tpu():
    out = _run(["chip_smoke.py"], ROOT)
    assert out.returncode != 0
    assert "no TPU present" in out.stderr
    first = out.stdout.splitlines()[0]
    assert first.startswith("[chip_smoke] platform=cpu")
    assert '"ok"' not in out.stdout


def test_chip_smoke_alone_prints_no_result(tmp_path):
    shutil.copy(os.path.join(ROOT, "chip_smoke.py"), tmp_path)
    out = _run(["chip_smoke.py"], tmp_path)
    assert out.returncode != 0
    assert '"ok"' not in out.stdout


def test_more_devices_than_visible_is_an_error():
    visible = len(jax.devices())
    with pytest.raises(ValueError, match="visible"):
        sim_devices(visible + 1)
    with pytest.raises(ValueError, match="visible"):
        make_lane_mesh(visible + 3)
    assert len(sim_devices(visible)) == visible


def test_compile_cache_follows_env(monkeypatch, tmp_path):
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert use_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == str(tmp_path)
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        assert use_compile_cache() == str(CHECKOUT_CACHE_DIR)
        assert jax.config.jax_compilation_cache_dir == str(CHECKOUT_CACHE_DIR)
        assert CHECKOUT_CACHE_DIR.parent.samefile(ROOT)
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)
        compilation_cache.reset_cache()


@pytest.mark.parametrize("name,n,codec,gates", [
    ("qft", 10, "device", None),
    ("qft", 10, "host", 12),
    ("qaoa", 9, "device", None),
])
def test_phase_simulator_tiny(name, n, codec, gates):
    m = chip_smoke.phase_simulator(name, n, codec, gates=gates)
    assert m["ok"], m
    assert m["gates"] == (gates or m["gates"])
    assert m["h2d_bytes"] > 0 and m["d2h_bytes"] > 0


def test_phase_service_tiny():
    m = chip_smoke.phase_service(8)
    assert m["ok"], m
    assert m["done"] == m["jobs"] == 4


def test_phase_sharded_tiny_on_four_host_devices():
    code = textwrap.dedent("""
        import json, sys
        sys.path.insert(0, ".")
        import jax, chip_smoke
        m = chip_smoke.phase_sharded(jax.devices(), 12, 10, 4)
        print("RESULT " + json.dumps(m))
    """)
    out = _run(["-c", code], ROOT, {
        "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
        "PYTHONPATH": os.path.join(ROOT, "src")})
    assert out.returncode == 0, out.stderr[-3000:]
    m = json.loads(out.stdout.split("RESULT ")[1])
    assert m["ok"], m
    assert m["block_mesh_handoffs"] > 0 and m["lanes_mesh_handoffs"] == 0
    assert min(m["block_mesh_rows"]) > 0 and min(m["lanes_mesh_rows"]) > 0


@pytest.mark.parametrize("qubits", [
    (0,), (8,), (12,), (18,), (3, 15), (15, 3), (9, 18), (7, 8), (0, 1),
    (2, 9, 14), (17, 11, 4),
])
def test_tiled_oracle_path_matches_apply_matrix(qubits):
    """The dense oracle's (8, 128)-tile-preserving gate application (used
    from n = 16 up) equals the reference transpose + GEMM path."""
    n = 19
    rng = np.random.default_rng(len(qubits) * 31 + qubits[0])
    x = (rng.standard_normal(2 ** n)
         + 1j * rng.standard_normal(2 ** n)).astype(np.complex64)
    K = 2 ** len(qubits)
    u = (rng.standard_normal((K, K))
         + 1j * rng.standard_normal((K, K))).astype(np.complex64)
    got = _apply_tiled(jnp.asarray(x), jnp.asarray(u), qubits, n)
    want = apply_matrix(jnp.asarray(x), jnp.asarray(u), qubits, n)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)

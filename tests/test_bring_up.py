"""Bring-up on the chip, rehearsed on the CPU: ``chip_smoke.py``'s phases at
the reduced preset, one device per pool instance (on four virtual CPU
devices), a process-mode frontend that never initializes a JAX backend, the
compile-cache location, the device peak table, kernel backend selection,
per-worker chip visibility and the serve CLI's exit code."""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import jax
import pytest

from repro.configs import get_config, serving_config
from repro.kernels import ops
from repro.runtime import compile_cache
from repro.runtime.hw import TPU_V5E, chip_for
from repro.serving.supervisor import _chip_env

ROOT = Path(__file__).resolve().parents[1]


def _python(code: str, tmp_path, *, devices: int = 1, timeout: float = 300):
    """Run ``code`` in a fresh CPU process (the smoke's phases own their
    process, as on the chip); returns its stdout."""
    env = dict(os.environ)
    env.update(PYTHONPATH=str(ROOT / "src"), JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jax_cache"))
    if devices > 1:
        env["XLA_FLAGS"] = (f"--xla_force_host_platform_device_count="
                            f"{devices}")
    proc = subprocess.run([sys.executable, "-c", code], cwd=ROOT, env=env,
                          capture_output=True, text=True, timeout=timeout)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    return proc.stdout


def _last_json(out: str):
    return json.loads(out.strip().splitlines()[-1])


# ---- chip_smoke.py, rehearsed ---------------------------------------------------

def test_chip_smoke_serve_phase_runs_every_path_at_parity(tmp_path):
    out = _last_json(_python(
        "import json, chip_smoke\n"
        "print(json.dumps(chip_smoke.phase_serve(published_widths=False, "
        "require_tpu=False, profile_len=256, long_len=400)))", tmp_path))
    assert out["served"] == 27
    assert all(out["steps"][p] >= 1 for p in
               ("fresh", "suffix", "packed_miss", "packed_hit")), out
    assert out["hit_tokens"] >= 9 * 256
    assert out["checked"] >= 20
    assert out["max_score_dev"] <= 2e-2


def test_chip_smoke_replica_phase_on_four_devices(tmp_path):
    out = _last_json(_python(
        "import json, chip_smoke\n"
        "print(json.dumps(chip_smoke.phase_replicas(published_widths=False, "
        "require_tpu=False, n_requests=12)))", tmp_path, devices=4))
    assert out["device"]["count"] == 4
    served = out["served_per_replica"]
    assert all(served[f"4xinst{i}"] >= 1 for i in range(4)), served
    assert served["1xinst0"] == 12
    assert out["max_score_dev"] <= 2e-2


@pytest.mark.parametrize("alone", [False, True])
def test_chip_smoke_refuses_to_report_ok_off_chip(tmp_path, alone):
    """Under JAX_PLATFORMS=cpu, and in a directory holding only the script,
    it exits non-zero and prints no verdict."""
    script = ROOT / "chip_smoke.py"
    if alone:
        script = Path(shutil.copy(script, tmp_path / "chip_smoke.py"))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jax_cache"))
    env.pop("PYTHONPATH", None)
    proc = subprocess.run([sys.executable, str(script)], cwd=script.parent,
                          env=env, capture_output=True, text=True,
                          timeout=300)
    assert proc.returncode != 0
    assert '"ok": true' not in proc.stdout


# ---- one device per instance, one process per chip ------------------------------

def test_pool_instances_hold_params_and_kv_on_their_own_device(tmp_path):
    out = _last_json(_python("""
import json, jax
from repro.launch.serve import make_pool
pool = make_pool("qwen1.5-0.5b", 4)
rows = []
for i, eng in enumerate(pool.engines.values()):
    eng.submit(list(range(1, 40 + i)), allowed_tokens=(5, 9))
    eng.run_until_drained()
    payloads = [b.payload for b in eng.cache.blocks.values()]
    leaves = jax.tree_util.tree_leaves((eng.params, payloads))
    rows.append({"device": str(eng.device), "blocks": len(payloads),
                 "on": sorted({str(d) for x in leaves for d in x.devices()})})
print(json.dumps(rows))
""", tmp_path, devices=4))
    assert len({r["device"] for r in out}) == 4
    for r in out:
        assert r["blocks"] >= 1
        assert r["on"] == [r["device"]], r


def test_process_mode_frontend_never_initializes_a_backend(tmp_path):
    out = _last_json(_python("""
import json
from repro.launch.serve import serve_trace
res = serve_trace(workers=1, max_requests=3, qps=50.0)
from jax._src import xla_bridge
print(json.dumps({"served": res["served"], "requests": res["requests"],
                  "backends": sorted(xla_bridge._backends)}))
""", tmp_path))
    assert out["served"] == out["requests"] == 3
    assert out["backends"] == []


def test_each_worker_slot_sees_its_own_chip(monkeypatch):
    monkeypatch.delenv("TPU_VISIBLE_CHIPS", raising=False)
    assert [_chip_env(s)["TPU_VISIBLE_CHIPS"] for s in range(4)] == \
        ["0", "1", "2", "3"]
    env = _chip_env(1)
    assert env["TPU_PROCESS_BOUNDS"] == env["TPU_CHIPS_PER_PROCESS_BOUNDS"] \
        == "1,1,1"
    assert env["TPU_PROCESS_PORT"] != _chip_env(0)["TPU_PROCESS_PORT"]
    monkeypatch.setenv("TPU_VISIBLE_CHIPS", "2,3")
    assert _chip_env(1)["TPU_VISIBLE_CHIPS"] == "3"
    with pytest.raises(RuntimeError, match="one per chip"):
        _chip_env(2)


# ---- config, cache, device tables ---------------------------------------------

def test_serving_config_is_the_one_preset_switch():
    full = serving_config("qwen1.5-0.5b", published_widths=True)
    assert full == get_config("qwen1.5-0.5b")
    assert full.hybrid_chunk == 2048 and full.d_model == 1024
    small = serving_config("qwen1.5-0.5b")
    assert small.num_layers == 4 and small.hybrid_chunk == 0


@pytest.fixture
def restore_cache_dir():
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_compile_cache_uses_the_env_dir(monkeypatch, tmp_path,
                                        restore_cache_dir):
    monkeypatch.setenv(compile_cache.ENV, str(tmp_path))
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == str(tmp_path)
    assert os.environ[compile_cache.ENV] == str(tmp_path)


def test_compile_cache_defaults_to_the_checkout(monkeypatch,
                                                restore_cache_dir):
    monkeypatch.setenv(compile_cache.ENV, "")   # undo restores the original
    monkeypatch.delenv(compile_cache.ENV)
    path = compile_cache.enable_compile_cache()
    assert path == str(ROOT / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path
    # exported, so that worker processes share the directory
    assert os.environ[compile_cache.ENV] == path


def test_chip_peaks_come_from_the_device_kind():
    assert chip_for("tpu", "TPU v5 lite") is TPU_V5E
    assert chip_for("cpu", "cpu") is TPU_V5E          # the CPU rehearsal
    with pytest.raises(ValueError, match="no peak rates"):
        chip_for("tpu", "TPU v4")
    with pytest.raises(ValueError, match="no peak rates"):
        chip_for("gpu", "NVIDIA H100")


@pytest.mark.parametrize("backend,interpret", [("tpu", False), ("cpu", True),
                                               ("gpu", None)])
def test_kernels_interpret_only_on_cpu(monkeypatch, backend, interpret):
    monkeypatch.setattr(ops.jax, "default_backend", lambda: backend)
    if interpret is None:
        with pytest.raises(RuntimeError, match="cannot run"):
            ops._interpret()
    else:
        assert ops._interpret() is interpret


# ---- the serve CLI's exit code ---------------------------------------------------

@pytest.mark.parametrize("chaos,code", [((), 1),
                                        (("--chaos-step-error", "0.5"), 0)])
def test_serve_exits_nonzero_when_a_request_errors(monkeypatch, capsys,
                                                   chaos, code):
    from repro.launch import serve
    monkeypatch.setattr(serve, "enable_compile_cache", lambda: "")
    monkeypatch.setattr(serve, "serve_trace", lambda *a, **k: {
        "requests": 2, "served": 1, "rejected": 1,
        "reject_reasons": {"error": 1}})
    monkeypatch.setattr(sys, "argv", ["serve", *chaos])
    if code:
        with pytest.raises(SystemExit) as e:
            serve.main()
        assert e.value.code == code
        assert "Rejected('error')" in capsys.readouterr().err
    else:
        serve.main()

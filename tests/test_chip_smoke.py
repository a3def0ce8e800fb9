"""chip_smoke.py's phases at smoke size on the CPU, kernels in interpret
mode, the four-chip phase on four virtual CPU devices.  The script itself
must refuse to run, and print no result, without a TPU.
"""
import importlib.util
import json
import os
import subprocess
import sys
import textwrap

import jax
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# a phase that goes through the launchers uses every device JAX has: run
# it in a process of its own, with exactly the devices it is meant for
_PHASE = textwrap.dedent("""
    import json, sys
    sys.path.insert(0, sys.argv[1])
    import chip_smoke
    out = getattr(chip_smoke, sys.argv[2])(sys.argv[3], smoke=True,
                                           **json.loads(sys.argv[4]))
    print("PHASE " + json.dumps(out, default=str))
""")


def _run_phase(tmp_path, phase, devices, **kwargs):
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"),
               JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}")
    r = subprocess.run([sys.executable, "-c", _PHASE, REPO, phase,
                        str(tmp_path), json.dumps(kwargs)],
                       capture_output=True, text=True, env=env, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    lines = [ln for ln in r.stdout.splitlines() if ln.startswith("PHASE ")]
    assert lines, r.stdout[-2000:]
    return json.loads(lines[-1][len("PHASE "):])


@pytest.fixture(scope="module")
def chip_smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(REPO, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_train_phase_resumes_bit_exact(tmp_path):
    out = _run_phase(tmp_path, "train_phase", 1, batch=4, seq=16)
    assert out["restored_step"] == 4
    assert out["resumed_final_loss"] == out["final_loss"]
    assert out["image_bytes"] > 0


def test_serve_phase_cold_restore_token_exact(tmp_path):
    out = _run_phase(tmp_path, "serve_phase", 1, batch=2, prompt=8,
                     max_seq=32, snapshot_at=4, tokens=8)
    assert out["snapshot_pos"] == 8 + 4
    assert out["generated"] == 8 + 1


def test_kernel_phase_interpret(chip_smoke):
    out = chip_smoke.kernel_phase(
        dict(attn=(1, 256, 4, 64), norm=(64, 128), ssd=(1, 256, 4, 64, 128),
             chunk=128), interpret=True)
    assert set(out["kernels"]) == {"flash_attention", "rmsnorm", "ssd_scan"}
    for rec in out["kernels"].values():
        assert rec["rel_err"] < 1e-3


def test_kernel_phase_rejects_a_wrong_kernel(chip_smoke, monkeypatch):
    import repro.kernels.rmsnorm as rn
    real = rn.rmsnorm
    monkeypatch.setattr(rn, "rmsnorm",
                        lambda x, s, **kw: real(x, s, **kw) * 1.1)
    with pytest.raises(chip_smoke.PhaseFailed, match="rmsnorm"):
        chip_smoke.kernel_phase(
            dict(attn=(1, 16, 2, 64), norm=(16, 128), ssd=(1, 128, 2, 64,
                                                           128), chunk=128),
            interpret=True)


@pytest.mark.slow
def test_multichip_phase_on_four_devices(tmp_path):
    out = _run_phase(tmp_path, "multichip_phase", 4, batch=8, seq=16)
    assert len(out["losses_2x2"]) == 6 and len(out["losses_4x1"]) == 2
    for key in ("initialized_2x2", "restored_4x1"):
        per = out[key]["param_bytes_per_device"]
        assert len(per) == 4
        assert max(per.values()) < out[key]["param_bytes"]


def test_main_refuses_without_a_tpu(chip_smoke, capsys):
    assert jax.devices()[0].platform == "cpu"
    assert chip_smoke.main([]) != 0
    assert chip_smoke.main(["--chips", "4"]) != 0
    out = capsys.readouterr()
    assert '"ok"' not in out.out
    assert "no TPU" in out.err


def test_compile_cache_dir(monkeypatch):
    from repro.launch import compile_cache
    prev = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
        jax.config.update("jax_compilation_cache_dir", None)
        assert compile_cache.use_compile_cache() == "/elsewhere"
        assert jax.config.jax_compilation_cache_dir is None
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        got = compile_cache.use_compile_cache()
        assert got == os.path.join(REPO, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == got
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)

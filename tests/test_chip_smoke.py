"""chip_smoke.py's phases on the CPU backend at the llama3 smoke size (the
kernels in interpret mode), its entry point's refusal to run without a
TPU, and the compile-cache helper it calls first."""
import json
import os
import shutil
import subprocess
import sys
import textwrap
from pathlib import Path

import jax

REPO = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO))

import chip_smoke  # noqa: E402
from repro.configs import get_config  # noqa: E402
from repro.launch import compile_cache  # noqa: E402

SMOKE = get_config("llama3-8b", smoke=True)
SIZES = dict(slots=2, max_len=64, lens=(5, 9), n_requests=5)


def test_kernel_phase_matches_oracles():
    cases = chip_smoke.kernel_cases(
        SMOKE, get_config("phi3.5-moe-42b-a6.6b", smoke=True),
        get_config("zamba2-2.7b", smoke=True), seq_len=64, slots=2,
        max_len=64)
    assert set(cases) == {"flash_attention", "decode_attention", "moe_gmm",
                          "ssd_scan"}
    assert chip_smoke.kernel_phase(cases, need_compiled=False)
    # on the CPU the kernels are interpreted, which the chip run refuses
    assert not chip_smoke.kernel_phase(cases, need_compiled=True)


def test_serve_phase_routes_engine_and_matches_reference(capsys):
    assert chip_smoke.serve_phase(SMOKE, **SIZES)
    out = capsys.readouterr().out
    assert "served 5/5 requests over 1 replica(s)" in out
    assert "logits vs float32 reference over 5 requests" in out


def test_reference_check_catches_wrong_logits():
    """The tap's logits are what the check compares: perturbing them past
    the tolerance must fail it."""
    model = chip_smoke.build_model(SMOKE)
    dev = jax.devices()[0]
    params = chip_smoke.init_params(model, 0, dev)
    engine = chip_smoke.ServeEngine(model, params, 2, 64, device=dev)
    tap = chip_smoke.LogitTap(engine)
    reqs = chip_smoke.make_requests(SMOKE.vocab_size, 0, (5, 9), 3)
    for r in reqs:
        engine.add_request(r)
    engine.run_until_drained()
    assert chip_smoke.reference_check(params, SMOKE, reqs, tap)
    rid = reqs[0].id
    tap.decode[rid] = tap.decode[rid][::-1].copy()
    assert not chip_smoke.reference_check(params, SMOKE, reqs, tap)


def test_entry_point_fails_without_tpu(capsys):
    assert jax.devices()[0].platform == "cpu"
    assert chip_smoke.main([]) != 0
    assert chip_smoke.main(["--chips", "4"]) != 0
    assert '"ok"' not in capsys.readouterr().out


def test_script_alone_fails_without_the_repo(tmp_path):
    shutil.copy(REPO / "chip_smoke.py", tmp_path / "chip_smoke.py")
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    out = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                         env=env, capture_output=True, text=True,
                         timeout=120)
    assert out.returncode != 0
    assert '"ok"' not in out.stdout


def test_replicas_phase_on_four_devices():
    """The --chips 4 path on four virtual CPU devices: one replica per
    device, each engine committed to its own device, agreeing with a
    single replica on device 0."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = str(REPO / "src")
    script = textwrap.dedent(f"""
        import json, sys
        sys.path.insert(0, {str(REPO)!r})
        import jax, chip_smoke
        from repro.configs import get_config
        cfg = get_config("llama3-8b", smoke=True)
        sizes = {dict(SIZES, n_requests=8)!r}
        ok = chip_smoke.replicas_phase(cfg, jax.devices()[:4], **sizes)
        print(json.dumps({{"ok": ok}}))
    """)
    out = subprocess.run([sys.executable, "-c", script], env=env, cwd=REPO,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-4000:]
    assert json.loads(out.stdout.splitlines()[-1]) == {"ok": True}, \
        out.stdout
    assert "requests per replica" in out.stdout


def test_compile_cache_defaults_to_a_fixed_dir_in_the_checkout(monkeypatch):
    old = jax.config.jax_compilation_cache_dir
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    try:
        got = compile_cache.enable_compile_cache()
        assert got == str(REPO / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == got
        assert ".jax_cache/" in (REPO / ".gitignore").read_text()
    finally:
        jax.config.update("jax_compilation_cache_dir", old)


def test_compile_cache_leaves_the_env_dir_to_jax(monkeypatch, tmp_path):
    old = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == old


def test_head_side_never_imports_jax():
    """One process per chip: the head (repro.core) must not load JAX, so
    only the process hosting a replica ever touches the device."""
    code = ("import sys, repro.core, repro.core.worker, repro.core.cluster; "
            "assert 'jax' not in sys.modules, 'repro.core imported jax'")
    env = dict(os.environ, PYTHONPATH=str(REPO / "src"))
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr[-2000:]

"""CPU rehearsal of ``chip_smoke.py``: its serve phases on the reduced
config with the kernels interpreted, its four-chip train phases on four
virtual CPU devices, and its refusal to run without a TPU."""
import os
import subprocess
import sys
import textwrap

import jax
import pytest

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
sys.path.insert(0, ROOT)

import chip_smoke  # noqa: E402


def _env(**extra):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    env.update(extra)
    return env


def test_serve_phases_rehearse_on_cpu():
    lines = []
    argv = chip_smoke.SERVE_ARGV + ["--reduced", "--requests", "6"]
    chip_smoke.serve_phases(argv, jax.devices()[:1], lines.append,
                            interpret=True)
    text = "\n".join(lines)
    assert "6 requests answered" in text
    assert "tokens identical to the uninterrupted pass" in text
    assert "kernel-path prefill logits within relative L2" in text
    assert "moe_expert_ffn kernel in float32 vs its float32 reference" \
        in text


def test_kernels_called_reads_names_off_tpu_custom_calls():
    class Lowered:
        def __init__(self, text):
            self.text = text

        def as_text(self):
            return self.text

    text = ('%0 = stablehlo.custom_call @tpu_custom_call(%a) '
            '{backend_config = "x", kernel_name = "flash_attention"}\n'
            '%1 = stablehlo.custom_call @other(%b) {kernel_name = "nope"}\n')
    assert chip_smoke.kernels_called(Lowered(text)) == {"flash_attention"}
    assert chip_smoke.kernels_called(Lowered("%0 = add %a, %b")) == set()


def test_train_phases_rehearse_on_four_cpu_devices():
    code = textwrap.dedent(f"""
        import sys
        sys.path.insert(0, {ROOT!r})
        import jax
        import chip_smoke
        chip_smoke.train_phases(jax.devices()[:4], print, reduced=True)
    """)
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=600, cwd=ROOT,
        env=_env(JAX_PLATFORMS="cpu",
                 XLA_FLAGS="--xla_force_host_platform_device_count=4"))
    assert out.returncode == 0, out.stderr[-3000:]
    assert "losses agree" in out.stdout
    assert "worlds [4, 4, 4, 2, 2, 2]" in out.stdout


def test_compile_cache_from_env_or_fixed_checkout_path(monkeypatch, tmp_path):
    from repro.launch import compile_cache
    was = jax.config.jax_compilation_cache_dir
    try:
        # set from outside: JAX reads the variable itself, code sets nothing
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert compile_cache.enable_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == was
        # unset: one fixed path inside the checkout
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        fixed = os.path.join(ROOT, ".jax_cache")
        assert compile_cache.enable_compile_cache() == fixed
        assert jax.config.jax_compilation_cache_dir == fixed
    finally:
        jax.config.update("jax_compilation_cache_dir", was)


@pytest.mark.parametrize("argv", [[], ["--four-chips"]])
def test_script_refuses_to_run_without_a_tpu(argv):
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "chip_smoke.py"), *argv],
        capture_output=True, text=True, timeout=300, cwd=ROOT,
        env=_env(JAX_PLATFORMS="cpu"))
    assert out.returncode != 0
    assert out.stdout == ""
    assert "needs a TPU" in out.stderr

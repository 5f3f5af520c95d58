"""`chip_smoke.py` phases at toy size on the CPU, and its CLI without a TPU.

On the CPU the kernels take their oracle/interpret paths, so the phases run
with `require_kernel=False`; what is checked here is the control flow and the
phases' own assertions (oracle bit-parity, accuracy rising, finite losses).
"""
import os
import subprocess
import sys

from repro.configs.base import ArchConfig

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
import chip_smoke  # noqa: E402

TOY_LM = ArchConfig(name="toy", family="dense", num_layers=2, d_model=32, num_heads=2,
                    num_kv_heads=1, d_ff=64, vocab_size=64, dtype="float32")


def test_kernel_phase_matches_oracle():
    chip_smoke.phase_kernels(leaf_shape=(33, 100), require_kernel=False)


def test_paper_phase_learns(small_task, capsys):
    chip_smoke.phase_paper(small_task)
    out = capsys.readouterr().out
    assert out.count("paper: round ") == 8 and "compile_seconds" in out


def test_lm_phase_toy(capsys):
    chip_smoke.phase_lm(TOY_LM, seq=16, require_kernel=False)
    out = capsys.readouterr().out
    assert out.count("lm: round ") == 2 and "peak_bytes_in_use" in out


def test_cli_without_tpu_exits_nonzero():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, "chip_smoke.py"], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=120)
    assert r.returncode != 0
    assert '"ok": true' not in r.stdout
    assert "no TPU" in r.stderr


def test_compile_cache_dir(monkeypatch, tmp_path):
    """$JAX_COMPILATION_CACHE_DIR wins untouched; else <checkout>/.jax_cache."""
    import jax

    from repro.utils import enable_compile_cache

    was = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == was
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    try:
        assert enable_compile_cache() == os.path.join(ROOT, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == os.path.join(ROOT, ".jax_cache")
    finally:
        jax.config.update("jax_compilation_cache_dir", was)

"""``chip_smoke.py`` off the chip: the no-fallback contract, the compile
cache rule, and a CPU rehearsal of its phases at a tiny size (Pallas
kernels in interpret mode, by explicit ``force``). A rehearsal that passes
here finds wrong paths and arguments; it says nothing about the chip."""

import importlib.util
import os
import subprocess
import sys

import jax
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(ROOT, "chip_smoke.py")


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SMOKE)
    mod = importlib.util.module_from_spec(spec)
    sys.modules["chip_smoke"] = mod     # dataclasses resolves the module
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def tiny(smoke):
    return smoke.Sizes(
        image=(32, 32, 3), classes=10, batch=8, fit_steps=3,
        epoch_batches=2, serve_batches=(1, 2),
        gen_width=32, gen_heads=2, gen_cache=32, gen_slots=2,
        gen_prompt_lens=(5, 9), gen_new_tokens=4,
        flash_shapes=((2, 2, 32, 16),), decode_shape=(2, 2, 64, 16),
        causal_shape=(1, 256, 4, 2, 16), causal_window=100, causal_block=128,
        page=8, verify_window=4, ln_shape=(32, 128),
        affine_shape=(64, 128), lstm_shape=(8, 16),
        kept_shape=(2, 32, 32, 4, 8, 48, 40, 2), dp_batch=8, dp_steps=2,
        dp_tol=0.5, lr=1e-3)


@pytest.mark.parametrize("args", [[], ["--chips", "4"]],
                         ids=["one_chip", "four_chips"])
def test_no_tpu_is_an_error_before_any_phase(args):
    """No CPU carry-on: without a TPU the script names what is missing,
    exits non-zero and prints no result line."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, SMOKE] + args, env=env, cwd=ROOT,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode != 0
    assert "no TPU" in r.stderr and "'cpu'" in r.stderr
    assert '"ok"' not in r.stdout and "smoke:" not in r.stdout


def test_compile_cache_dir_from_outside_is_left_alone(monkeypatch, tmp_path):
    from deeplearning4j_tpu import environment as envmod
    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: calls.append((k, v)))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    envmod.Environment()
    assert not [c for c in calls if c[0] == "jax_compilation_cache_dir"]
    # not set from outside: one fixed directory inside the checkout
    calls.clear()
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    envmod.Environment()
    assert ("jax_compilation_cache_dir",
            os.path.join(ROOT, ".jax_cache")) in calls
    assert envmod.COMPILE_CACHE_DIR == os.path.join(ROOT, ".jax_cache")
    monkeypatch.setenv("DL4J_TPU_COMPILE_CACHE", str(tmp_path))  # gone
    calls.clear()
    envmod.Environment()
    assert ("jax_compilation_cache_dir",
            os.path.join(ROOT, ".jax_cache")) in calls


def test_rehearse_train_and_predict(smoke, tiny):
    net = smoke.train_phase(tiny, jax.devices()[0])
    smoke.predict_phase(tiny, net)


def test_rehearse_generate(smoke, tiny):
    from deeplearning4j_tpu.ops import flash_attention as fa
    mode = fa.set_mode("force")       # interpret mode, asked for
    try:
        smoke.generate_phase(tiny)
    finally:
        fa.set_mode(mode)


def test_generate_on_auto_off_tpu_is_refused(smoke, tiny):
    """On ``auto`` the CPU takes the counted reference path, and the phase
    says so instead of passing."""
    with pytest.raises(AssertionError, match="did not take the fused"):
        smoke.generate_phase(tiny)


def test_rehearse_kernels(smoke, tiny):
    smoke.kernels_phase(tiny, interpret=True)


def test_rehearse_kept(smoke, tiny):
    smoke.kept_phase(tiny)


def test_rehearse_data_parallel(smoke, tiny):
    smoke.data_parallel_phase(tiny, jax.devices()[:4])

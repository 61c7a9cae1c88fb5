"""Runtime environment knobs.

TPU-native equivalent of libnd4j's ``Environment`` singleton + nd4j's
``ND4JSystemProperties``/``Nd4jEnvironmentVars`` (reference:
``libnd4j/include/system/Environment.h``†, ``nd4j-common``† per SURVEY.md §5
"Config / flag system"; reference mount was empty, citations
upstream-relative, unverified).

Env-var overrides use the ``DL4J_TPU_`` prefix (mirror of the reference's
``ND4J_``/``org.nd4j.*`` convention).

The load-bearing knob is **matmul precision policy**: DL4J is strict-fp32;
XLA's *default* matmul/conv precision decomposes f32 into bf16 passes
(~2.5e-3 rel err). The "auto" policy resolves per platform: CPU computes f32
at ``Precision.HIGHEST`` (exact oracle/grad-check parity, where CI runs);
TPU uses ``Precision.DEFAULT`` (measured on this backend: LeNet train step
compiles 25s vs 283s at HIGH with identical runtime — and bf16-pass f32 is
standard JAX training practice). Numeric-parity workloads on TPU opt in to
"high" (~2e-5 rel err) or "highest" via the env var or the instance
attribute. bfloat16 inputs always use native MXU passes (the perf path —
mixed-precision models opt in by dtype, per SURVEY.md §7.3 item 8).
"""

from __future__ import annotations

import os

import jax
from jax import lax


COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), ".jax_cache")


class Environment:
    _instance = None

    def __init__(self):
        self.debug = os.environ.get("DL4J_TPU_DEBUG", "0") == "1"
        self.verbose = os.environ.get("DL4J_TPU_VERBOSE", "0") == "1"
        # f32 matmul/conv precision policy:
        #   "auto"    => HIGHEST on CPU (exact oracle/grad-check parity),
        #                DEFAULT on TPU (single bf16 pass — measured on this
        #                backend: full LeNet step compiles 25s vs 283s at
        #                HIGH, runs identically; ~2.5e-3 conv rel err is
        #                standard JAX training practice)
        #   "highest" | "high" | "default" => force that lax.Precision
        #   (numeric-parity workloads on TPU set "high": ~2e-5 rel err)
        self.f32_matmul_precision = os.environ.get(
            "DL4J_TPU_F32_MATMUL_PRECISION", "auto")
        if self.f32_matmul_precision not in ("auto", "highest", "high", "default"):
            raise ValueError(
                f"DL4J_TPU_F32_MATMUL_PRECISION={self.f32_matmul_precision!r} "
                "— expected one of: auto, highest, high, default")
        # Persistent XLA compile cache. JAX_COMPILATION_CACHE_DIR, when set,
        # is JAX's own setting and nothing is set here; otherwise one fixed
        # directory inside the checkout (the path is part of the cache key,
        # so it never moves).
        if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
            jax.config.update("jax_compilation_cache_dir", COMPILE_CACHE_DIR)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)
        # NaN/Inf panic mode (ProfilerConfig.checkForNAN/INF equivalent):
        # routes to jax debug_nans/debug_infs.
        if os.environ.get("DL4J_TPU_CHECK_NAN", "0") == "1":
            jax.config.update("jax_debug_nans", True)
        if os.environ.get("DL4J_TPU_CHECK_INF", "0") == "1":
            jax.config.update("jax_debug_infs", True)
        # Default CNN data format for layers ("NCHW" = DL4J default; "NHWC"
        # is the TPU-preferred layout zoo/bench configs use).
        self.default_data_format = os.environ.get("DL4J_TPU_DATA_FORMAT", "NCHW")
        # XLA latency-hiding scheduler for the engines' TPU programs:
        # overlaps the async HBM copies (weight/activation layout
        # conversions) with compute. Measured ~3% faster ResNet-50 bf16
        # train step on v5e; harmless single-chip, designed for multi-chip
        # collective overlap. DL4J_TPU_LHS=0 disables.
        self.latency_hiding_scheduler = os.environ.get(
            "DL4J_TPU_LHS", "1") == "1"

    @classmethod
    def instance(cls) -> "Environment":
        if cls._instance is None:
            cls._instance = cls()
        return cls._instance

    def set_check_nan(self, enabled: bool) -> None:
        jax.config.update("jax_debug_nans", enabled)

    def set_check_inf(self, enabled: bool) -> None:
        jax.config.update("jax_debug_infs", enabled)


_DEFAULT_BACKEND = None  # cached: backend probing is the only expensive part


def engine_compiler_options():
    """``compiler_options`` for the engines' jitted train/epoch programs.

    TPU-only (CPU/GPU backends reject unknown TPU flags): enables the XLA
    latency-hiding scheduler unless Environment disables it. Returns None
    when there is nothing to apply (jax.jit treats None as default)."""
    global _DEFAULT_BACKEND
    if _DEFAULT_BACKEND is None:
        _DEFAULT_BACKEND = jax.default_backend()
    if _DEFAULT_BACKEND != "tpu":
        return None
    if not Environment.instance().latency_hiding_scheduler:
        return None
    return {"xla_tpu_enable_latency_hiding_scheduler": "true"}


def _resolved_f32_precision():
    """Resolve the policy — re-read per call so tests/users can flip
    ``Environment.instance().f32_matmul_precision`` at runtime."""
    global _DEFAULT_BACKEND
    mode = Environment.instance().f32_matmul_precision
    if mode == "auto":
        if _DEFAULT_BACKEND is None:
            _DEFAULT_BACKEND = jax.default_backend()
        mode = "highest" if _DEFAULT_BACKEND == "cpu" else "default"
    try:
        return {
            "highest": lax.Precision.HIGHEST,
            "high": lax.Precision.HIGH,
            "default": lax.Precision.DEFAULT,
        }[mode]
    except KeyError:
        raise ValueError(
            f"f32_matmul_precision={mode!r} — expected one of: "
            "auto, highest, high, default") from None


def precision_for(*arrays):
    """lax.Precision for a matmul/conv over these operands.

    float32/float64 anywhere -> the policy precision (see Environment); pure
    bf16/f16/int -> None (XLA default, native MXU passes).
    """
    import jax.numpy as jnp
    for a in arrays:
        dt = getattr(a, "dtype", None)
        if dt == jnp.float32 or dt == jnp.float64:
            return _resolved_f32_precision()
    return None

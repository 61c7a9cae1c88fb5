#!/usr/bin/env python3
"""chip_smoke.py: the quickest proof that the system still starts on the chip.

One process, one TPU v5e chip, the entry points a user would call:

1. device  - import the package, require a TPU (no CPU carry-on);
2. train   - ResNet-50 at its published size (224x224x3, 1000 classes,
             bf16 compute / f32 masters, batch 128): a few
             ``ComputationGraph.fit`` steps and one scanned
             ``fit_on_device`` epoch on batches made from a fixed seed;
3. serve   - ``JsonModelServer`` over that network answering ``/predict``
             over HTTP, then a ``generate=`` server over a
             ``SelfAttentionLayer`` stack answering ``/generate`` through
             the fused decode kernel, compared with the reference path;
4. kernels - every Pallas kernel on those paths, compiled on the device
             at a real width, against its ``reference_*`` function;
5. kept    - two steps of a small causal decoder whose attention output is
             as wide as its hidden size, one decoder layer recomputed at a
             time: ``attention.kept`` says the output is kept across the
             segment, and the step's ``memory_analysis()`` is printed with
             the output kept and with it recomputed.

``--chips 4`` runs only the data-parallel path instead: ResNet-50 under
``ParallelWrapper(shard_update=True)`` on a 4-device ``data`` mesh, and
the same seed and global batch on one device as what it is compared with.

A phase that fails raises, and the script exits non-zero with the
traceback. The timings printed on the ``smoke:`` lines are there to read
the run, not as measurements. On success the last line of stdout is one
JSON object: ``{"ok": true, "device": {"platform", "kind", "count"}}``.
Nothing here sets a compile cache: ``deeplearning4j_tpu.environment``
leaves ``JAX_COMPILATION_CACHE_DIR`` alone when it is set and otherwise
uses one fixed directory inside the checkout.
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import sys
import time
import urllib.request
from importlib import metadata

import numpy as np


@dataclasses.dataclass(frozen=True)
class Sizes:
    """What the run drives. The defaults are the real widths; the CPU
    rehearsal in ``tests/test_chip_smoke.py`` passes a tiny instance."""
    seed: int = 0
    image: tuple = (224, 224, 3)
    classes: int = 1000
    batch: int = 128
    fit_steps: int = 4
    epoch_batches: int = 2
    serve_batches: tuple = (1, 2, 4)          # inside the warmed 1/2/4
    gen_width: int = 768                       # 12 heads x 64
    gen_heads: int = 12
    gen_cache: int = 1024
    gen_slots: int = 4
    gen_prompt_lens: tuple = (40, 64, 100, 128)
    gen_new_tokens: int = 8
    # on Wq and Wk: at their initial size every key weighs the same, each
    # step sees the average of the context and greedy decoding repeats one
    # token; sharpened, the tokens vary, so equal tokens mean something
    gen_sharpen: float = 100.0
    flash_shapes: tuple = ((32, 12, 128, 64), (8, 12, 512, 64))
    causal_shape: tuple = (1, 4096, 12, 2, 128)   # B, T, q heads, KV heads, d
    causal_window: int = 512
    causal_block: int = 1024                   # the XLA path's
    decode_shape: tuple = (8, 12, 1024, 64)    # B, H, cache, d
    page: int = 16
    verify_window: int = 4
    ln_shape: tuple = (4096, 768)
    affine_shape: tuple = (128 * 56 * 56, 256)
    lstm_shape: tuple = (64, 256)
    # batch, positions, hidden, heads x head size (1x the hidden size),
    # feed-forward, vocabulary, layers
    kept_shape: tuple = (2, 4096, 1024, 8, 128, 2816, 4096, 2)
    dp_batch: int = 256                        # global; 64 a chip on four
    dp_steps: int = 3
    # at the real size the sharded step agrees with the one-device step to
    # well under this; at the rehearsal's size BatchNorm sees a handful of
    # values and reduction order alone moves the update by percents
    dp_tol: float = 3e-2
    lr: float = 0.01                           # the rehearsal's size needs less


def say(msg: str) -> None:
    print(f"smoke: {msg}", flush=True)


def close(name: str, got, want, tol: float) -> float:
    """Max abs difference over the reference's largest magnitude."""
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    if got.shape != want.shape:
        raise AssertionError(f"{name}: shape {got.shape} != {want.shape}")
    if not np.isfinite(got).all():
        raise AssertionError(f"{name}: non-finite values")
    err = float(np.max(np.abs(got - want)) / (np.max(np.abs(want)) + 1e-6))
    say(f"  {name}: rel err {err:.2e} (tol {tol:.0e})")
    if err > tol:
        raise AssertionError(f"{name}: rel err {err:.3e} > {tol:.0e}")
    return err


@contextlib.contextmanager
def parity_precision():
    """f32 matmuls at the repo's numeric-parity precision: on TPU the
    default policy runs them as one bf16 pass."""
    from deeplearning4j_tpu.environment import Environment
    env = Environment.instance()
    was, env.f32_matmul_precision = env.f32_matmul_precision, "highest"
    try:
        yield
    finally:
        env.f32_matmul_precision = was


class CacheEvents:
    """Counts JAX's persistent-compile-cache hits and misses."""

    def __init__(self):
        import jax
        self.hits = self.misses = 0
        jax.monitoring.register_event_listener(self._on)

    def _on(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1


# ---------------------------------------------------------------- 1. device
def device_phase(need: int) -> dict:
    import jax
    import jaxlib

    import deeplearning4j_tpu  # noqa: F401  (sets the compile cache policy)

    devs = jax.devices()
    platform = devs[0].platform
    if platform != "tpu":
        sys.exit(f"chip_smoke: no TPU: jax.devices() reports platform "
                 f"{platform!r} ({devs[0].device_kind!r} x{len(devs)}); "
                 "this script runs on the chip only")
    if len(devs) < need:
        sys.exit(f"chip_smoke: needs {need} TPU device(s), jax.devices() "
                 f"reports {len(devs)}")
    info = {"platform": platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    say(f"device {info} jax={jax.__version__} jaxlib={jaxlib.__version__} "
        f"libtpu={metadata.version('libtpu')} "
        f"cache_dir={jax.config.jax_compilation_cache_dir}")
    return info


# ----------------------------------------------------------------- 2. train
def _resnet(sz: Sizes):
    """ResNet-50, bf16 compute over f32 masters; momentum, so that there is
    updater state to place and to shard."""
    from deeplearning4j_tpu.models.resnet import resnet50
    from deeplearning4j_tpu.nn.updaters import Nesterovs
    return resnet50(num_classes=sz.classes, input_shape=sz.image,
                    updater=Nesterovs(learning_rate=sz.lr, momentum=0.9),
                    seed=sz.seed, dtype="BFLOAT16").init()


def _image_batch(sz: Sizes, n: int, salt: int):
    rng = np.random.default_rng(sz.seed + salt)
    x = rng.standard_normal((n,) + tuple(sz.image), dtype=np.float32)
    y = np.eye(sz.classes, dtype=np.float32)[rng.integers(0, sz.classes, n)]
    return x, y


def _on_device(tree, device) -> bool:
    import jax
    return all(leaf.devices() == {device} for leaf in jax.tree.leaves(tree))


def train_phase(sz: Sizes, device):
    import jax

    net = _resnet(sz)
    before = jax.tree.map(np.asarray, net.params)
    x, y = _image_batch(sz, sz.batch, salt=1)

    losses, times = [], []
    for _ in range(sz.fit_steps):          # the per-batch path, one batch
        t0 = time.perf_counter()
        net.fit(x, y)
        jax.block_until_ready(net.params)
        times.append(time.perf_counter() - t0)
        losses.append(float(net.score()))
    say(f"train fit: first call {times[0]:.1f}s (compile included), then "
        f"{min(times[1:]) * 1e3:.1f} ms/step with host batches; "
        f"losses {[round(l, 4) for l in losses]}")
    if not np.isfinite(losses).all():
        raise AssertionError(f"non-finite fit loss: {losses}")
    if not losses[-1] < losses[0]:
        raise AssertionError(f"loss did not fall on the repeated batch: "
                             f"{losses}")

    xs = np.concatenate([x] * sz.epoch_batches)   # the scanned path
    ys = np.concatenate([y] * sz.epoch_batches)
    t0 = time.perf_counter()
    hist = net.fit_on_device(xs, ys, epochs=1, batch_size=sz.batch)
    first = time.perf_counter() - t0
    t0 = time.perf_counter()
    hist2 = net.fit_on_device(xs, ys, epochs=1, batch_size=sz.batch)
    again = time.perf_counter() - t0
    say(f"train fit_on_device: first epoch {first:.1f}s (compile "
        f"included), second {again * 1e3 / sz.epoch_batches:.1f} ms/step "
        f"upload included; losses {np.round(hist, 4).tolist()} "
        f"{np.round(hist2, 4).tolist()}")
    if hist.shape != (sz.epoch_batches,) or not np.isfinite(hist).all() \
            or not np.isfinite(hist2).all():
        raise AssertionError(f"fit_on_device losses: {hist} {hist2}")
    if not hist2[-1] < losses[0]:
        raise AssertionError(f"scanned epochs did not keep the loss under "
                             f"the first step's: {hist2} vs {losses[0]}")

    moved = jax.tree.map(lambda a, b: not np.array_equal(a, np.asarray(b)),
                         before, net.params)
    if not all(jax.tree.leaves(moved)):
        raise AssertionError("some parameters did not change")
    if not (_on_device(net.params, device)
            and _on_device(net.updater_state, device)):
        raise AssertionError(f"parameters do not live on {device}")
    return net


# ----------------------------------------------------------------- 3. serve
def _post(port: int, path: str, payload: dict):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}", data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"})
    opener = urllib.request.build_opener(urllib.request.ProxyHandler({}))
    with opener.open(req, timeout=600) as resp:
        return resp.status, json.loads(resp.read())


def _compile_events() -> int:
    from deeplearning4j_tpu.runtime import telemetry
    return int(telemetry.registry.get("compile.events").total())


def predict_phase(sz: Sizes, net):
    from deeplearning4j_tpu.serving.server import JsonModelServer

    top = max(sz.serve_batches)
    x, _ = _image_batch(sz, top, salt=2)
    t0 = time.perf_counter()
    srv = JsonModelServer(net, max_batch_size=top, warmup=True)
    say(f"serve warmup: buckets up to {top} in "
        f"{time.perf_counter() - t0:.1f}s")
    want = np.asarray(net.output(x))             # direct, the top bucket
    if want.shape != (top, sz.classes):
        raise AssertionError(f"net.output shape {want.shape}")
    warm = (srv.inference.engine.compiles, _compile_events())
    with srv:
        for b in sz.serve_batches:
            t0 = time.perf_counter()
            status, body = _post(srv.port, "/predict",
                                 {"data": x[:b].tolist()})
            ms = (time.perf_counter() - t0) * 1e3
            if status != 200:
                raise AssertionError(f"/predict batch {b}: {status} {body}")
            close(f"/predict batch {b} ({ms:.0f} ms, JSON included)",
                  body["output"], want[:b], 2e-2)
    if (srv.inference.engine.compiles, _compile_events()) != warm:
        raise AssertionError("a /predict request compiled after warmup")


def _attention_stack(sz: Sizes):
    import jax

    from deeplearning4j_tpu.nn.config import (InputType,
                                              NeuralNetConfiguration)
    from deeplearning4j_tpu.nn.layers.attention import SelfAttentionLayer
    from deeplearning4j_tpu.nn.layers.core import DenseLayer, OutputLayer
    from deeplearning4j_tpu.nn.model import MultiLayerNetwork

    w, h = sz.gen_width, sz.gen_heads
    conf = (NeuralNetConfiguration.builder().seed(sz.seed)
            .input_type(InputType.recurrent(w, max(sz.gen_prompt_lens)))
            .list(SelfAttentionLayer(n_out=w, n_heads=h),
                  DenseLayer(n_out=w, activation="relu"),
                  SelfAttentionLayer(n_out=w, n_heads=h),
                  OutputLayer(n_out=w, activation="softmax"))
            .build())
    net = MultiLayerNetwork(conf).init()
    net.params = jax.tree_util.tree_map_with_path(
        lambda path, a: a * sz.gen_sharpen
        if path[-1].key in ("Wq", "Wk") else a, net.params)
    return net


def _step_log_probs(net, sz: Sizes, prompt):
    """One prompt prefilled and one token decoded through a fresh engine,
    under whatever dispatch mode is set: the two steps' log-probabilities,
    each centred (the logits, up to rounding)."""
    from deeplearning4j_tpu.serving.engine import GenerativeEngine

    eng = GenerativeEngine(net, slots=sz.gen_slots)
    one_hot = np.eye(sz.gen_width, dtype=np.float32)
    state, first = eng.prefill(eng.new_state(sz.gen_cache), one_hot[prompt],
                               len(prompt), 0)
    x_t = np.zeros((sz.gen_slots, 1, sz.gen_width), np.float32)
    x_t[0, 0] = one_hot[int(np.argmax(first))]
    active = np.zeros(sz.gen_slots, np.int32)
    active[0] = 1
    _, step = eng.decode(state, x_t, active)
    logp = np.log(np.stack([first, step[0]]))
    return logp - logp.mean(axis=1, keepdims=True)


def generate_phase(sz: Sizes):
    """``/generate`` through the fused decode kernel, then the same prompts
    through the reference path on a fresh engine. The f32 stack runs at
    the repo's numeric-parity precision so that both paths pick the same
    greedy tokens; then, at the precision a user gets by default, the
    fused and the reference paths' logits for a prefill and a decode step
    are compared to a tolerance."""
    from deeplearning4j_tpu.ops import flash_attention as fa
    from deeplearning4j_tpu.serving import ContinuousBatcher
    from deeplearning4j_tpu.serving.server import JsonModelServer

    net = _attention_stack(sz)
    rng = np.random.default_rng(sz.seed + 3)
    prompts = [rng.integers(0, sz.gen_width, n).tolist()
               for n in sz.gen_prompt_lens]
    with parity_precision():
        cfg = dict(slots=sz.gen_slots, max_cache_len=sz.gen_cache,
                   min_cache_len=sz.gen_cache,
                   max_new_tokens=sz.gen_new_tokens)

        fa.reset_counters()
        t0 = time.perf_counter()
        srv = JsonModelServer(net, generate=cfg)
        say(f"generate warmup: {srv.generator.engine.compiles} programs in "
            f"{time.perf_counter() - t0:.1f}s")
        warm = (srv.generator.engine.compiles, _compile_events())
        fused = []
        with srv:
            for p in prompts:
                t0 = time.perf_counter()
                status, body = _post(srv.port, "/generate", {"tokens": p})
                if status != 200 or len(body["tokens"]) != sz.gen_new_tokens:
                    raise AssertionError(f"/generate: {status} {body}")
                fused.append(body["tokens"])
                say(f"  /generate prompt {len(p)} -> {body['tokens']} "
                    f"({(time.perf_counter() - t0) * 1e3:.0f} ms)")
        if (srv.generator.engine.compiles, _compile_events()) != warm:
            raise AssertionError("a /generate request compiled after warmup")
        c = fa.counters()
        say(f"  dispatch {({k: v for k, v in c.items() if v})}")
        bad = {k: v for k, v in c.items() if v and (
            k.startswith("decode_fallback") or k == "fallback_platform")}
        if not c["decode_fused"] > 0 or bad:
            raise AssertionError(f"decode did not take the fused kernel: {c}")

        mode = fa.set_mode("off")
        try:
            fa.reset_counters()
            ref = ContinuousBatcher(net, **cfg)
            want = [ref.submit(tokens=p).result(timeout=600)["tokens"]
                    for p in prompts]
            ref.shutdown()
            c = fa.counters()
            if c["decode_fused"] or not c["decode_fallback_mode"] > 0:
                raise AssertionError(f"the reference run was not the "
                                     f"reference path: {c}")
        finally:
            fa.set_mode(mode)
        if fused != want:
            raise AssertionError(f"fused tokens {fused} != reference "
                                 f"tokens {want}")
        distinct = len({t for toks in fused for t in toks})
        say(f"  {len(prompts)} prompts x {sz.gen_new_tokens} tokens equal "
            f"to the reference path's ({distinct} distinct tokens)")
        if distinct < 2 * len(prompts):
            raise AssertionError(f"the prompts' tokens hardly vary: {fused}")

    fa.reset_counters()
    got = _step_log_probs(net, sz, prompts[-1])
    c = fa.counters()
    if not (c["fused"] > 0 and c["decode_fused"] > 0):
        raise AssertionError(f"the fused path was not taken: {c}")
    mode = fa.set_mode("off")
    try:
        fa.reset_counters()
        want = _step_log_probs(net, sz, prompts[-1])
    finally:
        fa.set_mode(mode)
    c = fa.counters()
    if c["fused"] or c["decode_fused"] or not c["decode_fallback_mode"] > 0:
        raise AssertionError(f"the reference run was not the reference "
                             f"path: {c}")
    close("prefill + decode step logits at the default precision, fused "
          "against reference", got, want, 2e-2)


# --------------------------------------------------------------- 4. kernels
def kernels_phase(sz: Sizes, interpret: bool = False):
    """Each Pallas kernel of the paths above, jitted on the device at a
    real width, against its reference. ``interpret`` is for the CPU
    rehearsal; on the chip the kernels compile."""
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.ops import autotune
    from deeplearning4j_tpu.ops import flash_attention as fa
    from deeplearning4j_tpu.ops import fused_epilogues as fe
    from deeplearning4j_tpu.ops import nnops
    from deeplearning4j_tpu.ops import pallas_kernels as pk

    rng = np.random.default_rng(sz.seed + 4)
    bf16, f32 = jnp.bfloat16, jnp.float32

    def arr(shape, dtype=bf16, scale=0.5):
        return jnp.asarray(rng.standard_normal(shape, dtype=np.float32)
                           * scale, dtype)

    def total(o):
        return jnp.sum(o.astype(jnp.float32))

    for shape in sz.flash_shapes:
        B, H, T, d = shape
        q, k, v = arr(shape), arr(shape), arr(shape)
        keep = np.ones((B, T), np.float32)
        keep[:, T - T // 4:] = 0.0             # a padded tail, as BERT masks
        mask = jnp.where(jnp.asarray(keep)[:, None, None, :] > 0, 0.0,
                         jnp.float32(np.finfo(np.float32).min))
        for bias, tag in ((None, "no bias"), (mask, "key bias")):
            def kern(q, k, v):
                return fa.flash_attention(q, k, v, bias, interpret=interpret)

            def ref(q, k, v):
                return fa.reference_attention(q, k, v, bias)

            name = f"flash {list(shape)} {tag}"
            close(f"{name} fwd", jax.jit(kern)(q, k, v),
                  jax.jit(ref)(q, k, v), 2e-2)
            got = jax.jit(jax.grad(lambda *a: total(kern(*a)),
                                   argnums=(0, 1, 2)))(q, k, v)
            want = jax.jit(jax.grad(lambda *a: total(ref(*a)),
                                    argnums=(0, 1, 2)))(q, k, v)
            for g, w, n in zip(got, want, "qkv"):
                close(f"{name} d{n}", g, w, 4e-2)

    # masked attention over grouped KV heads: the kernels the dispatcher
    # takes on the chip against the blocked XLA path it takes elsewhere
    from deeplearning4j_tpu.ops import causal_attention as ca
    from deeplearning4j_tpu.runtime import telemetry as tel
    B, T, H, KV, d = sz.causal_shape
    q, k, v = arr((B, T, H, d)), arr((B, T, KV, d)), arr((B, T, KV, d))
    for window, kind in ((None, "full"), (sz.causal_window, "window")):
        def both(mode):
            # a function of its own for each mode: jit would hand a second
            # wrapper of the same function the first one's trace
            def attend(q, k, v):
                return ca.causal_attention(q, k, v, window=window,
                                           block=sz.causal_block)

            old = fa.set_mode(mode)
            try:
                return (jax.jit(attend)(q, k, v),) + jax.jit(jax.grad(
                    lambda *a: total(attend(*a)), argnums=(0, 1, 2)))(q, k, v)
            finally:
                fa.set_mode(old)

        counter = tel.registry.get("attention.dispatch")
        before = counter.value(kind=kind, decision="kernel")
        got, want = both("force" if interpret else "auto"), both("off")
        took = counter.value(kind=kind, decision="kernel") - before
        say(f"  attention.dispatch{{kind={kind},decision=kernel}} +{took}")
        if took < 1:
            raise AssertionError(f"{kind} attention did not take the kernel")
        name = f"causal {kind} {[B, T, H, d]} over {KV} KV heads"
        for g, w, n in zip(got, want, ("fwd", "dq", "dk", "dv")):
            close(f"{name} {n}", g, w, 2e-2 if n == "fwd" else 4e-2)

    B, H, C, d = sz.decode_shape
    q1, kc, vc = arr((B, H, 1, d)), arr((B, H, C, d)), arr((B, H, C, d))
    lengths = jnp.asarray(rng.integers(C // 4, C, B), jnp.int32)
    close(f"decode {[B, H, 1, d]} x {C} contiguous",
          jax.jit(lambda *a: fa.decode_attention(*a, interpret=interpret))(
              q1, kc, vc, lengths),
          fa.reference_decode_attention(q1, kc, vc, lengths), 2e-2)

    P = sz.page                                # the same cache, paged
    mp = C // P
    table = jnp.asarray(1 + rng.permutation(B * mp).reshape(B, mp),
                        jnp.int32)             # page 0 is the zero page
    rows = fa.page_rows(table.reshape(-1), P)
    flat = jnp.transpose(kc, (0, 2, 1, 3)).reshape(B * C, H, d), \
        jnp.transpose(vc, (0, 2, 1, 3)).reshape(B * C, H, d)
    pools = [jnp.zeros(((1 + B * mp) * P, H, d), bf16).at[rows].set(f)
             for f in flat]

    def paged(q, kp, vp, table, lengths):
        return fa.decode_attention(
            q, fa.paged_gather(kp, table, P), fa.paged_gather(vp, table, P),
            lengths, interpret=interpret, page=P)

    close(f"decode {[B, H, 1, d]} x {C} page={P}",
          jax.jit(paged)(q1, *pools, table, lengths),
          fa.reference_decode_attention(q1, kc, vc, lengths), 2e-2)

    Tq = sz.verify_window
    qw = arr((B, H, Tq, d))
    base = jnp.minimum(lengths, C - Tq)
    close(f"multi-query verify Tq={Tq} x {C}",
          jax.jit(lambda *a: fa.decode_multiquery_attention(
              *a, interpret=interpret))(qw, kc, vc, base),
          fa.reference_decode_multiquery(qw, kc, vc, base), 2e-2)

    def epilogue(name, shape, kern, ref, vec_dtype):
        R, Cc = shape
        x = arr(shape)
        g = jnp.asarray(1.0 + 0.1 * rng.standard_normal((1, Cc)), vec_dtype)
        b = jnp.asarray(0.1 * rng.standard_normal((1, Cc)), vec_dtype)
        close(f"{name} {list(shape)} fwd", jax.jit(kern)(x, g, b),
              jax.jit(ref)(x, g, b), 2e-2)
        got = jax.jit(jax.grad(lambda *a: total(kern(*a)),
                               argnums=(0, 1, 2)))(x, g, b)
        want = jax.jit(jax.grad(lambda *a: total(ref(*a)),
                                argnums=(0, 1, 2)))(x, g, b)
        for gg, w, n in zip(got, want, ("dx", "dscale", "dshift")):
            close(f"{name} {list(shape)} {n}", gg, w, 4e-2)

    R, Cc = sz.ln_shape
    br = fe.row_block(R, fe._row_mult(bf16))
    epilogue(
        "_ln_act gelu", sz.ln_shape,
        lambda x, g, b: fe._ln_act(x, g, b, 1e-12, "gelu", br, interpret),
        lambda x, g, b: fe.reference_act("gelu")(nnops.layer_norm(
            x.astype(f32), g[0].astype(f32), b[0].astype(f32), 1e-12)
        ).astype(x.dtype), bf16)
    R, Cc = sz.affine_shape
    br = fe.row_block(R, fe._row_mult(bf16))
    epilogue(
        "_affine_act relu", sz.affine_shape,
        lambda x, s, b: fe._affine_act(x, s, b, "relu", br, interpret),
        lambda x, s, b: fe.reference_act("relu")(
            x.astype(f32) * s + b).astype(x.dtype), f32)

    B, U = sz.lstm_shape
    cell = [arr((B, U), f32), arr((B, U), f32), arr((B, U), f32),
            arr((U, 4 * U), f32, 0.05), arr((U, 4 * U), f32, 0.05),
            arr((4 * U,), f32, 0.05)]
    got = jax.jit(lambda *a: pk.lstm_cell_fused(
        *a, forget_bias=1.0, interpret=interpret))(*cell)
    with parity_precision():
        want = jax.jit(lambda *a: nnops.lstm_cell(*a, forget_bias=1.0))(*cell)
    close(f"lstm cell {[B, U]} h", got[0], want[0], 2e-2)
    close(f"lstm cell {[B, U]} c", got[1], want[1], 2e-2)

    # one sweep, so that the autotuner's own timing loop has run here too
    entry = autotune.sweep(1, C, d, bf16, True, decode=True,
                           interpret=interpret, repeats=2)
    say(f"  autotune decode sweep: {entry['candidates']} -> "
        f"{entry['blocks']}")


# ------------------------------------------------------- --chips 4: DP path
def kept_phase(sz: Sizes):
    """A decoder whose heads' output is as wide as its input, recomputed a
    layer at a time: the segments keep that output (``attention.kept``),
    two ``fit_on_device`` steps train, and against the same stack with the
    rule answering no (there is no switch: the rule reads shapes) the
    losses agree and the compiled step's memory is printed for both."""
    from unittest import mock

    from deeplearning4j_tpu.models.decoder_stack import (decoder_stack,
                                                         vertices_per_layer)
    from deeplearning4j_tpu.nn.layers import decoder
    from deeplearning4j_tpu.runtime import telemetry as tel

    B, T, hidden, heads, head, ffn, vocab, layers = sz.kept_shape
    ids = np.random.default_rng(sz.seed + 5).integers(
        0, vocab, (B, T), dtype=np.int32)
    counter = tel.registry.get("attention.kept")

    def run():
        net = decoder_stack(
            vocab_size=vocab, hidden_size=hidden, n_layers=layers, eps=1e-6,
            attention=lambda i: decoder.CausalSelfAttentionLayer(
                n_heads=heads, n_kv_heads=heads, head_size=head),
            mlp=lambda i: decoder.GatedDenseLayer(n_hidden=ffn), seq_len=T,
            dtype="BFLOAT16", seed=sz.seed,
            workspace_mode=f"every_{vertices_per_layer()}").init()
        t0 = time.perf_counter()
        # the labels are not read: the head takes the next token of the ids
        losses = [float(x) for x in net.fit_on_device(
            ids, np.ones((B, 1), np.float32), epochs=2, batch_size=B)]
        took = time.perf_counter() - t0
        report = net.memory_report(B)
        return losses, took, {k: report[k] for k in (
            "temp_bytes", "peak_bytes", "activation_bytes")}

    before = counter.value(kind="full", decision="kept")
    kept, took, kept_mem = run()
    n = counter.value(kind="full", decision="kept") - before
    say(f"  attention.kept{{kind=full,decision=kept}} +{n}; losses {kept} "
        f"in {took:.1f}s; step memory {kept_mem}")
    if n < layers:
        raise AssertionError("a 1x-wide attention layer did not keep its "
                             "output under every_<k>")
    before = counter.value(kind="full", decision="recomputed", why="wide")
    with mock.patch.object(decoder, "_keeps_output", lambda *a: False):
        again, took, again_mem = run()
    n = counter.value(kind="full", decision="recomputed",
                      why="wide") - before
    say(f"  attention.kept{{kind=full,decision=recomputed,why=wide}} +{n}; "
        f"losses {again} in {took:.1f}s; step memory {again_mem}")
    if not all(np.isfinite(kept)) or len(kept) != 2:
        raise AssertionError(f"two finite losses expected, got {kept}")
    close("kept against recomputed losses", kept, again, 1e-3)
    if kept_mem["activation_bytes"] is not None:
        # what the backward pass is handed: the tagged outputs and no more
        extra = kept_mem["activation_bytes"] - again_mem["activation_bytes"]
        want = layers * B * T * heads * head * 2
        say(f"  kept for the backward pass: {extra} bytes more "
            f"({layers} x [{B}, {T}, {heads * head}] bf16 = {want})")
        if extra != want:
            raise AssertionError("the segments keep more than the tagged "
                                 "outputs")


def _device_bytes(tree) -> dict:
    """Bytes each device holds of ``tree``, by device."""
    import jax
    out: dict = {}
    for leaf in jax.tree.leaves(tree):
        for s in leaf.addressable_shards:
            out[s.device] = out.get(s.device, 0) + s.data.nbytes
    return out


def data_parallel_phase(sz: Sizes, devices):
    """ResNet-50 under ``ParallelWrapper(shard_update=True)`` on a 4-device
    ``data`` mesh against the same seed and global batch on one device."""
    import jax

    from deeplearning4j_tpu.data.dataset import DataSet
    from deeplearning4j_tpu.parallel.data_parallel import (ParallelWrapper,
                                                           make_mesh)

    from deeplearning4j_tpu.ops import fused_epilogues as fe

    n = len(devices)
    x, y = _image_batch(sz, sz.dp_batch, salt=5)

    def run(fit, net):
        losses, times = [], []
        for _ in range(sz.dp_steps):
            t0 = time.perf_counter()
            fit()
            jax.block_until_ready(net.params)
            times.append(time.perf_counter() - t0)
            losses.append(float(net.score()))
        return losses, times

    # the comparison first, while device 0 holds nothing else: the whole
    # global batch on one chip takes most of its memory
    one = _resnet(sz)
    single, t = run(lambda: one.fit(x, y), one)
    say(f"one device, global batch {sz.dp_batch}: first call {t[0]:.1f}s "
        f"(compile included), then {min(t[1:]) * 1e3:.1f} ms/step with "
        f"host batches; losses {[round(l, 4) for l in single]}")
    del one

    fe.reset_counters()
    net = _resnet(sz)
    pw = ParallelWrapper(net, mesh=make_mesh(devices), shard_update=True)
    data = DataSet(x, y)
    dp, t = run(lambda: pw.fit(data), net)
    say(f"data-parallel x{n}, same seed and global batch: first call "
        f"{t[0]:.1f}s (compile included), then {min(t[1:]) * 1e3:.1f} "
        f"ms/step with host batches; losses {[round(l, 4) for l in dp]}")
    say(f"  epilogue kernel decisions in the partitioned step "
        f"{({k: v for k, v in fe.counters().items() if v})}")
    if not np.isfinite(dp + single).all():
        raise AssertionError(f"non-finite loss: {dp} {single}")
    if not dp[-1] < dp[0]:
        raise AssertionError(f"data-parallel loss did not fall: {dp}")
    close("data-parallel losses vs one device", dp, single, sz.dp_tol)

    want = set(devices)
    for name, tree in (("parameters", net.params),
                       ("updater state", net.updater_state)):
        for leaf in jax.tree.leaves(tree):
            if {s.device for s in leaf.addressable_shards} != want:
                raise AssertionError(f"{name}: a leaf of shape {leaf.shape} "
                                     f"lives on {leaf.devices()}")
    full = sum(leaf.nbytes for leaf in jax.tree.leaves(net.updater_state))
    held = _device_bytes(net.updater_state)
    share = {str(d): round(b / full, 4) for d, b in held.items()}
    say(f"  updater state {full} bytes; share held by each device {share}")
    if max(held.values()) > 0.3 * full:
        raise AssertionError(f"updater state is not spread 1/{n}: {share}")
    p_share = {str(d): round(b / sum(l.nbytes for l in
                                     jax.tree.leaves(net.params)), 4)
               for d, b in _device_bytes(net.params).items()}
    say(f"  parameters replicated; share held by each device {p_share}")

    text = pw._lower_step(sz.dp_batch).as_text()
    found = {op: text.count(op) for op in
             ("all-reduce", "reduce-scatter", "all-gather")}
    say(f"  collectives in the compiled step {found}")
    if not (found["all-reduce"] or found["reduce-scatter"]) \
            or not found["all-gather"]:
        raise AssertionError(f"expected gradient reduction and a parameter "
                             f"all-gather in the step: {found}")


# --------------------------------------------------------------------- main
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the data-parallel path and its "
                         "one-device comparison on four chips")
    args = ap.parse_args(argv)
    t0 = time.perf_counter()
    info = device_phase(args.chips)
    import jax
    cache = CacheEvents()
    sz = Sizes()
    if args.chips == 4:
        data_parallel_phase(sz, jax.devices()[:4])
    else:
        net = train_phase(sz, jax.devices()[0])
        predict_phase(sz, net)
        del net
        generate_phase(sz)
        kernels_phase(sz)
        kept_phase(sz)
    say(f"compile cache: {cache.hits} hits, {cache.misses} misses; "
        f"total {time.perf_counter() - t0:.0f}s")
    print(json.dumps({"ok": True, "device": info}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""The Pallas kernels of the main path, compiled for a described TPU v5e
at real widths. Interpret mode (every other kernel test) never runs the
TPU lowering's block-shape, layout and VMEM checks; these compiles do, at
no chip time. Nothing executes: a compile that passes is not a chip run.

The topology is described inside a module-scoped fixture (one process at
a time may load the TPU's library, and xdist workers all import this
file), compiles run in this process, and the persistent compile cache is
off around them (such entries cannot be read back without a chip).
"""

import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from deeplearning4j_tpu.ops import flash_attention as fa
from deeplearning4j_tpu.ops import fused_epilogues as fe
from deeplearning4j_tpu.ops import pallas_kernels as pk


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _custom_call_names(text):
    """The instruction names of the compiled text's custom calls."""
    return re.findall(r"%([\w.\-]+) = [^\n]*? custom-call\(", text)


def _compile(fn, one_chip, *avals, kernels):
    """Compile ``fn`` for the described chip on ``(shape, dtype)`` avals and
    return the compiled text; each of ``kernels`` must be in it under its
    ``pallas_call(name=)``, which is what a device trace, the benchmark's
    readers and the ledger's breakdown call it."""
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in avals]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    calls = _custom_call_names(text)
    for kernel in kernels:
        assert any(kernel in c for c in calls), (kernel, calls)
    return text


BF16 = jnp.bfloat16


@pytest.mark.parametrize("grad", [False, True], ids=["fwd", "fwd_bwd"])
@pytest.mark.parametrize("has_bias", [False, True], ids=["nobias", "keybias"])
@pytest.mark.parametrize("shape", [(32, 12, 128, 64), (8, 12, 512, 64)],
                         ids=["bert_b32_s128", "b8_s512"])
def test_flash_attention_compiles(one_chip, shape, has_bias, grad):
    B, H, T, d = shape
    bq, bk = fa.pick_block(T), fa.pick_kv_block(T, has_bias=has_bias)
    assert fa.fits_vmem_attention(bq, bk, d, 2)

    def fwd(q, k, v, *bias):
        return fa.flash_attention(q, k, v, bias[0] if bias else None,
                                  block_q=bq, block_k=bk)

    def loss(q, k, v, *bias):
        # sin keeps the forward in the program: a whole-row backward reads
        # no output of it
        return jnp.sum(jnp.sin(fwd(q, k, v, *bias).astype(jnp.float32)))

    fn = jax.grad(loss, argnums=(0, 1, 2)) if grad else fwd
    avals = [(shape, BF16)] * 3
    if has_bias:
        avals.append(((B, 1, 1, T), jnp.float32))
    # an explicit 128 is the whole row at s128 (forward and one fused
    # backward) and a blocked grid at s512 (forward, dq, dk/dv)
    backward = ("flash_bwd_dkv",) if bk == T \
        else ("flash_bwd_dq", "flash_bwd_dkv")
    text = _compile(fn, one_chip, *avals, kernels=(
        ("flash_fwd",) + backward if grad else ("flash_fwd",)))
    assert text.count("tpu_custom_call") >= (1 + len(backward) if grad else 1)


@pytest.mark.parametrize("grad", [False, True], ids=["fwd", "fwd_bwd"])
@pytest.mark.parametrize("shape,dtype,blocks", [
    ((32, 12, 512, 64), BF16, (512, 512)),     # bert_base.finetune.s512
    ((128, 12, 128, 64), BF16, (128, 128)),    # the same tokens at s128
    ((8, 12, 512, 64), jnp.float32, (512, 512)),
    ((1, 12, 8192, 64), BF16, (512, 4096)),    # a row that does not fit
], ids=["bert_b32_s512", "bert_b128_s128", "f32_s512", "b1_s8192"])
def test_flash_attention_default_tiling_compiles(one_chip, monkeypatch,
                                                 shape, dtype, blocks, grad):
    """The cells' own shapes with a key bias at the tiling the dispatcher
    chooses, through ``attention()`` as a traced program reaches it: a
    whole-row tile (twelve heads a grid step at s128) with its fused
    backward under ``flash_bwd_dkv``, and the blocked grid of 512 x 4,096
    where 8,192 keys do not fit beside 512 queries. The loss keeps the forward alive (the whole-row backward
    needs no output of it)."""
    from deeplearning4j_tpu.ops import autotune as at
    B, H, T, d = shape
    assert fa.default_blocks(T, T, d, np.dtype(dtype).itemsize, True) \
        == blocks
    monkeypatch.setattr(fa, "_tpu_available", lambda: True)
    at.reset()
    fa.reset_counters()
    fa._TILING.zero()

    def fwd(q, k, v, bias):
        return fa.attention(q, k, v, bias)

    def loss(q, k, v, bias):
        return jnp.sum(jnp.sin(fwd(q, k, v, bias).astype(jnp.float32)))

    whole = blocks[1] == T
    kernels = ("flash_fwd",) if not grad else (
        ("flash_fwd", "flash_bwd_dkv") if whole
        else ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"))
    text = _compile(jax.grad(loss, argnums=(0, 1, 2)) if grad else fwd,
                    one_chip, (shape, dtype), (shape, dtype), (shape, dtype),
                    ((B, 1, 1, T), jnp.float32), kernels=kernels)
    at.reset()
    assert fa.counters()["fused"] == 1
    assert fa._TILING.value(kind="whole_row" if whole else "blocked") == 1
    calls = _custom_call_names(text)
    if grad and whole:
        assert not any("flash_bwd_dq" in c for c in calls)
        # dk first: [G, T, d], which is how the benchmark's reader finds it
        assert re.search(r"flash_bwd_dkv[\w.\-]* = \((bf16|f32)\[%d,%d,%d\]"
                         % (B * H, T, d), text)
    # no lane-replicated statistics leave a whole-row kernel
    assert (f"f32[{B * H},{T},128]" in text) == (not whole)


@pytest.mark.parametrize("page", [0, 16], ids=["contiguous", "page16"])
def test_decode_attention_compiles(one_chip, page):
    B, H, C, d = 8, 12, 1024, 64

    def fn(q, k, v, lengths):
        return fa.decode_attention(q, k, v, lengths, block_k=128, page=page)

    _compile(fn, one_chip, ((B, H, 1, d), BF16), ((B, H, C, d), BF16),
             ((B, H, C, d), BF16), ((B,), jnp.int32), kernels=("flash_fwd",))


def test_paged_decode_step_compiles(one_chip):
    """The paged decode step as serving runs it: insert through the page
    table, gather, then the decode kernel."""
    B, H, d, P, MP = 8, 12, 64, 16, 64
    rows = (B * MP + 1) * P

    def fn(q, kn, vn, kp, vp, table, lengths):
        kp2 = fa.paged_insert(kp, kn, lengths, table, P)
        vp2 = fa.paged_insert(vp, vn, lengths, table, P)
        kf = fa.paged_gather(kp2, table, P)
        vf = fa.paged_gather(vp2, table, P)
        return fa.decode_attention(q, kf, vf, lengths + 1, block_k=128,
                                   page=P)

    tok = ((B, H, 1, d), BF16)
    pool = ((rows, H, d), BF16)
    _compile(fn, one_chip, tok, tok, tok, pool, pool,
             ((B, MP), jnp.int32), ((B,), jnp.int32), kernels=("flash_fwd",))


def test_decode_multiquery_compiles(one_chip):
    B, H, C, d, Tq = 8, 12, 1024, 64, 4

    def fn(q, k, v, lengths):
        return fa.decode_multiquery_attention(q, k, v, lengths, block_k=128)

    _compile(fn, one_chip, ((B, H, Tq, d), BF16), ((B, H, C, d), BF16),
             ((B, H, C, d), BF16), ((B,), jnp.int32),
             kernels=("flash_decode_mq",))


@pytest.mark.parametrize("grad", [False, True], ids=["fwd", "fwd_bwd"])
def test_layer_norm_act_compiles(one_chip, grad):
    R, C = 4096, 768
    br = fe.row_block(R, fe._row_mult(BF16))
    assert fe.fits_vmem_epilogue(br, C, 2, "ln")

    def fwd(x, g, b):
        return fe._ln_act(x, g, b, 1e-12, "gelu", br, False)

    def loss(x, g, b):
        return jnp.sum(fwd(x, g, b).astype(jnp.float32))

    fn = jax.grad(loss, argnums=(0, 1, 2)) if grad else fwd
    _compile(fn, one_chip, ((R, C), BF16), ((1, C), BF16), ((1, C), BF16),
             kernels=("layer_norm_act_fwd",) + (("layer_norm_act_bwd",)
                                               if grad else ()))


@pytest.mark.parametrize("grad", [False, True], ids=["fwd", "fwd_bwd"])
def test_affine_act_compiles(one_chip, grad):
    R, C = 128 * 56 * 56, 256          # ResNet-50 stage-1 activations, b128
    br = fe.row_block(R, fe._row_mult(BF16))
    assert fe.fits_vmem_epilogue(br, C, 2, "affine")

    def fwd(x, s, b):
        return fe._affine_act(x, s, b, "relu", br, False)

    def loss(x, s, b):
        return jnp.sum(fwd(x, s, b).astype(jnp.float32))

    fn = jax.grad(loss, argnums=(0, 1, 2)) if grad else fwd
    _compile(fn, one_chip, ((R, C), BF16), ((1, C), jnp.float32),
             ((1, C), jnp.float32),
             # the sum's gradient needs no forward output: only the
             # backward kernel is left in that program
             kernels=("affine_act_bwd",) if grad else ("affine_act_fwd",))


#: the decoder cells' attention layers at their published widths:
#: (q heads, KV heads, scored width, value width, window, kind)
CAUSAL_LAYOUTS = {
    "full48": (48, 8, 128, 128, None, "full"),          # laguna_xs2
    "window64": (64, 8, 128, 128, 512, "window"),       # laguna_xs2
    "latent32": (32, 32, 192, 128, None, "latent"),     # kanana2_30b_a3b
}


@pytest.mark.parametrize("layout", list(CAUSAL_LAYOUTS))
def test_causal_flash_compiles(one_chip, monkeypatch, layout):
    """The masked kernels as the cells' steps reach them, two sequences of
    8,192, forward and backward through ``causal_attention`` with a TPU in
    sight: the three kernels under their names, and nothing in the program
    that is [T, T] or a [rows, 1024, T] block of scores. The grouped layouts
    take them in ``auto``; one query head a KV head goes to XLA there
    (``why=ungrouped``) and compiles them under ``force``."""
    from deeplearning4j_tpu.ops import causal_attention as ca
    from deeplearning4j_tpu.runtime import telemetry as tel
    H, KV, d, dv, window, kind = CAUSAL_LAYOUTS[layout]
    B, T = 2, 8192
    monkeypatch.setattr(ca, "_tpu_available", lambda: True)
    monkeypatch.setattr(fa, "_tpu_available", lambda: True)
    counter = tel.registry.get("attention.dispatch")

    def loss(q, k, v):
        return jnp.sum(jnp.sin(ca.causal_attention(
            q, k, v, window=window, kind=kind).astype(jnp.float32)))

    avals = (((B, T, H, d), BF16), ((B, T, KV, d), BF16),
             ((B, T, KV, dv), BF16))
    if H == KV:
        went = dict(kind=kind, decision="blocked_rows", why="ungrouped")
        before = counter.value(**went)
        jax.eval_shape(loss, *(jax.ShapeDtypeStruct(*a) for a in avals))
        assert counter.value(**went) == before + 1
        monkeypatch.setattr(fa, "_state", {"mode": "force"})
    before = counter.value(kind=kind, decision="kernel")
    text = _compile(jax.grad(loss, argnums=(0, 1, 2)), one_chip, *avals,
                    kernels=("causal_flash_fwd", "causal_flash_bwd_dq",
                             "causal_flash_bwd_dkv"))
    assert counter.value(kind=kind, decision="kernel") == before + 1
    assert not re.search(rf"\[(\d+,)*{T},{T}\]", text)
    assert not re.search(rf"f32\[(\d+,)*1024,{T}\]", text)
    # the statistics the backward keeps: one compact row a (head, sequence)
    assert f"f32[{B * H},1,{T}]" in text


@pytest.fixture
def xla_attention():
    """The blocked XLA path, whatever the platform: ``off``."""
    old = fa.set_mode("off")
    yield
    fa.set_mode(old)


@pytest.mark.parametrize("heads,window", [(48, None), (64, 512)],
                         ids=["full48", "window64"])
def test_blocked_causal_attention_compiles(one_chip, xla_attention, heads,
                                           window):
    """Laguna-XS.2's two attention layers at their published head counts over
    8 KV heads of 128: the blocked XLA path (the kernels' fallback), forward
    and backward, with no [T, T] array anywhere in the program."""
    from deeplearning4j_tpu.ops import causal_attention as ca
    T = 4096

    def loss(q, k, v):
        return jnp.sum(ca.causal_attention(q, k, v, window=window)
                       .astype(jnp.float32))

    args = [jax.ShapeDtypeStruct(s, BF16, sharding=one_chip)
            for s in ((1, T, heads, 128), (1, T, 8, 128), (1, T, 8, 128))]
    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(*args) \
        .compile().as_text()
    assert not re.search(rf"\[(\d+,)*{T},{T}\]", text)


def test_blocked_latent_attention_compiles(one_chip, xla_attention):
    """kanana-2's latent attention as the blocked XLA path sees it: 32 heads,
    one query head a KV head, scores over 192 channels beside values of 128,
    forward and backward, with no [T, T] array anywhere in the program."""
    from deeplearning4j_tpu.ops import causal_attention as ca
    T = 4096

    def loss(q, k, v):
        return jnp.sum(ca.causal_attention(q, k, v, kind="latent")
                       .astype(jnp.float32))

    args = [jax.ShapeDtypeStruct(s, BF16, sharding=one_chip)
            for s in ((1, T, 32, 192), (1, T, 32, 192), (1, T, 32, 128))]
    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(*args) \
        .compile()
    text = compiled.as_text()
    assert not re.search(rf"\[(\d+,)*{T},{T}\]", text)
    # a row's keys are kept once, not once a block: no stacked slice of them
    assert not re.search(rf"bf16\[32,{T - 1024},192\]", text)


def test_indexer_and_selection_compile_without_a_sort(one_chip):
    """Keye-VL-2.0's indexer at its published sizes (16 index heads of 64
    against one index key, the 2,048 best of up to 8,192 keys a query): the
    blocked index scores and the selection that counts its way to each row's
    2,048th score, with no ``[16, T, T]`` array, no float ``[T, T]`` buffer
    and no sort anywhere in the program; the one ``[T, T]`` array is the mask. None of its results has a
    shape by which ``moe_time_pct`` tells the expert layers' fusions."""
    import json
    from benchmarks.metrics import moe_time_pct
    from deeplearning4j_tpu.ops import sparse_attention as sa
    B, T = 2, 8192
    args = [jax.ShapeDtypeStruct(s, t, sharding=one_chip) for s, t in (
        ((B, T, 16, 64), BF16), ((B, T, 64), BF16), ((B, T, 16), jnp.float32))]
    compiled = jax.jit(lambda q, k, w: sa.open_keys(q, k, w, 2048)) \
        .lower(*args).compile()
    text = compiled.as_text()
    assert f"pred[{B},{T},{T}]" in text
    assert not re.search(rf"\[(\d+,)*16,{T},{T}\]", text)
    # beside the mask (134 MB) the program holds a block's products at most:
    # one float32 [B, T, T] would be 537 MB
    assert compiled.memory_analysis().temp_size_in_bytes < 2 ** 28
    assert " sort(" not in text and "TopK" not in text
    with open(os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "benchmarks", "configs",
            "keye_vl2_30b_a3b.json")) as f:
        cfg = json.load(f)
    shapes = moe_time_pct.patterns(cfg, {"batch": B, "seq_len": T})
    results = re.findall(r"\n\s*(?:ROOT )?%[\w.\-]+ = (\(?[a-z0-9]+\[[^=]*?) "
                         r"(?:fusion|convolution|copy|reduce)\(", text)
    assert len(results) > 20
    assert not [r for r in results if shapes.search(r)]


def test_selected_attention_compiles_backward(one_chip, monkeypatch):
    """Keye-VL-2.0's selected attention (32 query heads on 4 KV heads of
    128, two sequences of 8,192) forward and backward through
    ``causal_attention`` with a TPU in sight: the masked kernels under
    their names with the mask as an operand, the 8 query heads of a KV head
    in one tile that fits VMEM, and no ``[.., 1024, T]`` or ``[T, T]`` block
    of float scores anywhere in the program."""
    from deeplearning4j_tpu.ops import causal_attention as ca
    from deeplearning4j_tpu.runtime import telemetry as tel
    B, T, H, KV, d = 2, 8192, 32, 4, 128
    monkeypatch.setattr(ca, "_tpu_available", lambda: True)
    monkeypatch.setattr(fa, "_tpu_available", lambda: True)
    counter = tel.registry.get("attention.dispatch")

    def loss(q, k, v, select):
        return jnp.sum(jnp.sin(ca.causal_attention(q, k, v, select=select)
                               .astype(jnp.float32)))

    before = counter.value(kind="sparse", decision="kernel")
    text = _compile(jax.grad(loss, argnums=(0, 1, 2)), one_chip,
                    ((B, T, H, d), BF16), ((B, T, KV, d), BF16),
                    ((B, T, KV, d), BF16), ((B, T, T), jnp.bool_),
                    kernels=("causal_flash_fwd", "causal_flash_bwd_dq",
                             "causal_flash_bwd_dkv"))
    assert counter.value(kind="sparse", decision="kernel") == before + 1
    assert not re.search(rf"(f32|bf16)\[(\d+,)*{T},{T}\]", text)
    assert not re.search(rf"(f32|bf16)\[(\d+,)*1024,{T}\]", text)
    # the mask reaches the kernels as bytes, and transposed for dk/dv
    assert f"s8[{B},{T},{T}]" in text
    assert f"f32[{B * H},1,{T}]" in text           # the compact logsumexp
    bq, bk = ca.causal_blocks(T, d, d, None, 2, H // KV)
    assert fa.fits_vmem_attention(H // KV * bq, bk, d, 2, mask_rows=bq)


def test_held_experts_compile_as_grouped_products(one_chip):
    """16 experts of 2048 x 512 held, 8 of 256 chosen a token: the chunk
    loop with the compiler's ragged-dot kernels, forward and backward."""
    from deeplearning4j_tpu.ops import moe
    N, d, f, held, k = 4096, 2048, 512, 16, 8

    def loss(x, wr, w1, w3, w2):
        top_e, w = moe.route(x, wr, k, 2.5)
        order, ends, _ = moe.plan(top_e, 0, held)
        out, _ = moe.held_experts(x, w, w1, w3, w2, order, ends, 2560, k)
        return jnp.sum(out)

    avals = [((N, d), BF16), ((d, 256), BF16), ((held, d, f), BF16),
             ((held, d, f), BF16), ((held, f, d), BF16)]
    args = [jax.ShapeDtypeStruct(s, t, sharding=one_chip) for s, t in avals]
    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4))).lower(*args) \
        .compile().as_text()
    assert "ragged-dot" in text


def test_lstm_cell_compiles(one_chip):
    B, U = 64, 256
    assert pk.fits_vmem(B, U, U)
    f32 = jnp.float32
    _compile(pk.lstm_cell_fused, one_chip, ((B, U), f32), ((B, U), f32),
             ((B, U), f32), ((U, 4 * U), f32), ((U, 4 * U), f32),
             ((4 * U,), f32), kernels=("lstm_cell",))


def test_data_parallel_step_compiles_for_the_mesh(topo, one_chip,
                                                  monkeypatch):
    """A Mosaic kernel cannot be partitioned by GSPMD, so the step that
    ``ParallelWrapper`` traces routes the epilogue kernels to their
    reference path, counted. The same network's one-device step holds no
    kernel either: in ``auto`` a convolution's feature map takes XLA's own
    epilogue (``fallback_conv_layout``), so one chip and four run the same
    program. The dispatchers ask the backend, which is the CPU here: the
    test steers them onto their TPU branch."""
    from jax.sharding import Mesh
    from deeplearning4j_tpu.nn import memory
    from deeplearning4j_tpu.nn.config import (InputType,
                                              NeuralNetConfiguration)
    from deeplearning4j_tpu.nn.layers.conv import (BatchNormalization,
                                                   ConvolutionLayer)
    from deeplearning4j_tpu.nn.layers.core import (ActivationLayer,
                                                   OutputLayer)
    from deeplearning4j_tpu.nn.model import MultiLayerNetwork
    from deeplearning4j_tpu.nn.updaters import Nesterovs
    from deeplearning4j_tpu.parallel.data_parallel import ParallelWrapper
    from deeplearning4j_tpu.runtime import sentinel

    _on_the_chip(monkeypatch)
    conf = (NeuralNetConfiguration.builder().seed(0).data_type("BFLOAT16")
            .updater(Nesterovs(learning_rate=0.01, momentum=0.9))
            .input_type(InputType.convolutional(3, 16, 16,
                                                data_format="NHWC"))
            .list(ConvolutionLayer(n_out=128, kernel=(3, 3), mode="same",
                                   activation="identity",
                                   data_format="NHWC"),
                  BatchNormalization(data_format="NHWC"),
                  ActivationLayer(activation="relu"),
                  OutputLayer(n_out=8))
            .build())
    net = MultiLayerNetwork(conf).init()

    fe.reset_counters()
    pw = ParallelWrapper(net, mesh=Mesh(np.array(topo.devices), ("data",)),
                         shard_update=True)
    text = pw._lower_step(64).as_text()
    assert "tpu_custom_call" not in text and "all-reduce" in text
    assert fe.counters()["fallback_gspmd"] > 0

    fe.reset_counters()
    x, y = memory._batch_avals(net, 64)
    args = (jax.eval_shape(lambda: net.params),
            jax.eval_shape(lambda: net.updater_state),
            jax.eval_shape(lambda: net.state),
            jax.ShapeDtypeStruct((), np.int32),
            jax.eval_shape(lambda: jax.random.PRNGKey(0)), x, y, None, None,
            sentinel.counter_avals())
    args = jax.tree.map(lambda a: jax.ShapeDtypeStruct(
        a.shape, a.dtype, sharding=one_chip), args)
    text = net._build_train_step(1).lower(*args).compile().as_text()
    assert "tpu_custom_call" not in text
    c = fe.counters()
    assert c["fallback_conv_layout"] > 0
    assert c["fused"] == 0 and c["fallback_gspmd"] == 0

    # a one-device program still carries an epilogue kernel where the
    # dispatcher keeps one: a rank-2 LayerNorm site, through the dispatcher
    fe.reset_counters()
    _compile(lambda x, g, b: fe.layer_norm_act(x, g, b, 1e-12, act="gelu"),
             one_chip, ((4096, 768), BF16), ((768,), BF16), ((768,), BF16),
             kernels=("layer_norm_act_fwd",))
    assert fe.counters()["fused"] == 1


def test_the_scope_table_of_a_step_compiled_for_the_chip(one_chip):
    """``telemetry.program_scopes`` on the TPU compiler's text (tiled
    layouts, tuple results, fusions named after one of the instructions
    fused into them): a train step's weight-gradient kernels are the
    ``multiply_reduce_fusion f32[]`` of the device traces, the sentinel's
    sum of squares riding the product as the tuple's first result, and
    their ``phases_inside`` says so (PERF.md section 5, corrected in PR 34)."""
    from deeplearning4j_tpu.nn import memory
    from deeplearning4j_tpu.nn.config import (InputType,
                                              NeuralNetConfiguration)
    from deeplearning4j_tpu.nn.layers.core import DenseLayer, OutputLayer
    from deeplearning4j_tpu.nn.model import MultiLayerNetwork
    from deeplearning4j_tpu.nn.updaters import Adam
    from deeplearning4j_tpu.runtime import sentinel
    from deeplearning4j_tpu.runtime import telemetry as tel

    conf = (NeuralNetConfiguration.builder().seed(3).data_type("BFLOAT16")
            .updater(Adam(learning_rate=1e-2))
            .input_type(InputType.feed_forward(256))
            .list(DenseLayer(n_out=512, activation="tanh", name="hidden"),
                  DenseLayer(n_out=256, activation="relu"),
                  OutputLayer(n_out=128))
            .build())
    net = MultiLayerNetwork(conf).init()
    x, y = memory._batch_avals(net, 256)
    args = (jax.eval_shape(lambda: net.params),
            jax.eval_shape(lambda: net.updater_state),
            jax.eval_shape(lambda: net.state),
            jax.ShapeDtypeStruct((), np.int32),
            jax.eval_shape(lambda: jax.random.PRNGKey(0)), x, y, None, None,
            sentinel.counter_avals())
    args = jax.tree.map(lambda a: jax.ShapeDtypeStruct(
        a.shape, a.dtype, sharding=one_chip), args)
    compiled = net._build_train_step(1).lower(*args).compile()
    tel.reset_programs()
    tel.record_program("train.step", compiled.runtime_executable(),
                       **net._program_labels())
    table, = tel.program_scopes()
    tel.reset_programs()
    ins = table["instructions"]
    assert {i["phase"] for i in ins.values()} >= {
        "forward", "backward", "updater", "sentinel"}
    assert {i["vertex"] for i in ins.values()} >= {"hidden", "layer1",
                                                   "layer2"}
    # every shape is dtype[dims]: no layout, no tiling, no tuple
    assert all(re.fullmatch(r"([a-z][a-z0-9]*\[[^\]{}()]*\])?", i["shape"])
               for i in ins.values())
    riding = [i for n, i in ins.items()
              if n.startswith("multiply_reduce_fusion")
              and i["shape"] == "f32[]"
              and {"backward", "sentinel"} <= set(i["phases_inside"])]
    assert len(riding) >= 3, sorted(ins)[:40]


def _on_the_chip(monkeypatch):
    """The dispatchers ask the backend, which is the CPU here: steer them
    onto their TPU branch for a lowering meant for the described chip."""
    monkeypatch.setattr(fa, "_tpu_available", lambda: True)
    monkeypatch.setattr(fe, "_tpu_available", lambda: True)
    fa.reset_counters()
    fe.reset_counters()


def _attention_lm(width=768, heads=12):
    from deeplearning4j_tpu.nn.config import (InputType,
                                              NeuralNetConfiguration)
    from deeplearning4j_tpu.nn.layers.attention import SelfAttentionLayer
    from deeplearning4j_tpu.nn.layers.core import DenseLayer, OutputLayer
    from deeplearning4j_tpu.nn.model import MultiLayerNetwork
    conf = (NeuralNetConfiguration.builder().seed(0)
            .input_type(InputType.recurrent(width, 128))
            .list(SelfAttentionLayer(n_out=width, n_heads=heads),
                  DenseLayer(n_out=width, activation="relu"),
                  OutputLayer(n_out=width, activation="softmax"))
            .build())
    return MultiLayerNetwork(conf).init()


@pytest.mark.parametrize("axes,model", [((1, 4), 4), ((4, 1), 1)],
                         ids=["tensor_parallel", "data_only"])
def test_generative_serving_compiles_for_the_mesh(topo, monkeypatch, axes,
                                                  model):
    """Prefill and decode of a serving engine on the described 2x2 mesh,
    12 heads x 64 over a 1024-long cache. GSPMD partitions both programs,
    so no kernel is handed to it: prefill takes the reference path; decode
    runs its kernel per shard inside a shard_map when a model axis divides
    the heads, and the reference path on a mesh without one. Counted."""
    from jax.sharding import Mesh
    from deeplearning4j_tpu.serving.engine import GenerativeEngine
    _on_the_chip(monkeypatch)
    mesh = Mesh(np.array(topo.devices).reshape(axes), ("data", "model"))
    eng = GenerativeEngine(_attention_lm(), slots=8, mesh=mesh)
    assert eng.stats()["tp_shards"] == model
    prefill = eng._prefill_exe(128, 1024).as_text()
    decode = eng._decode_exe(1024).as_text()
    c = {k: v for k, v in fa.counters().items() if v}
    assert "tpu_custom_call" not in prefill
    if model > 1:
        assert "tpu_custom_call" in decode and "all-reduce" in decode
        assert c == {"fallback_gspmd": 1, "decode_tp_shard_map": 1,
                     "decode_fused": 1}
    else:
        assert "tpu_custom_call" not in decode
        assert c == {"fallback_gspmd": 1, "decode_fallback_gspmd": 1}
    assert not fe.counters()["fused"]


def test_paged_tensor_parallel_decode_compiles_for_the_mesh(topo,
                                                            monkeypatch):
    """The paged engine's decode step and speculative verify (Tq=4) on a
    4-way model axis: ``page=16`` kernels, three heads a shard, inside the
    dispatcher's shard_map."""
    from jax.sharding import Mesh
    from deeplearning4j_tpu.serving.engine import PagedGenerativeEngine
    _on_the_chip(monkeypatch)
    mesh = Mesh(np.array(topo.devices).reshape(1, 4), ("data", "model"))
    eng = PagedGenerativeEngine(_attention_lm(), slots=8, pages=512,
                                page_size=16, max_cache_len=1024, mesh=mesh)
    for tq in (1, 4):
        assert "tpu_custom_call" in eng._pdecode_exe(tq, 64).as_text()
    c = {k: v for k, v in fa.counters().items() if v}
    assert c == {"decode_tp_shard_map": 1, "decode_fused": 1,
                 "decode_multiquery_tp_shard_map": 1,
                 "decode_multiquery": 1}


def test_one_shot_serving_compiles_for_a_data_mesh(topo, monkeypatch):
    """``InferenceEngine(mesh=...)`` on a data-only mesh shards the batch
    and replicates the parameters: a partitioned program all the same, so
    the conv epilogues take the reference path. The engine's own lowering,
    fed sharding trees in place of placed arrays (nothing can be placed on
    a described device)."""
    from jax.sharding import Mesh
    from deeplearning4j_tpu.nn.config import (InputType,
                                              NeuralNetConfiguration)
    from deeplearning4j_tpu.nn.layers.conv import (BatchNormalization,
                                                   ConvolutionLayer)
    from deeplearning4j_tpu.nn.layers.core import (ActivationLayer,
                                                   OutputLayer)
    from deeplearning4j_tpu.nn.model import MultiLayerNetwork
    from deeplearning4j_tpu.serving.engine import InferenceEngine
    _on_the_chip(monkeypatch)
    conf = (NeuralNetConfiguration.builder().seed(0).data_type("BFLOAT16")
            .input_type(InputType.convolutional(3, 56, 56,
                                                data_format="NHWC"))
            .list(ConvolutionLayer(n_out=256, kernel=(3, 3), mode="same",
                                   activation="identity",
                                   data_format="NHWC"),
                  BatchNormalization(data_format="NHWC"),
                  ActivationLayer(activation="relu"),
                  OutputLayer(n_out=1000))
            .build())
    net = MultiLayerNetwork(conf).init()
    eng = InferenceEngine(net, mesh=Mesh(np.array(topo.devices), ("data",)))
    pl = eng._placement_layer
    monkeypatch.setattr(eng, "_params_placement", lambda: (
        "described", pl.param_shardings(net.params),
        pl.state_shardings(net.state)))
    text = eng._lower_bucket(*eng._bucket_avals(128, None)).compile() \
        .as_text()
    assert "tpu_custom_call" not in text
    assert fe.counters()["fallback_gspmd"] > 0 and not fe.counters()["fused"]


def test_compiler_params_declare_the_grid():
    """The grid's parallel axes reach the compiler (they were once dropped
    in silence behind a renamed class and an ``except``)."""
    from jax.experimental.pallas import tpu as pltpu
    assert fa._compiler_params(pltpu).dimension_semantics == (
        "parallel", "parallel", "arbitrary")
    assert fe._compiler_params_rows(pltpu).dimension_semantics == (
        "arbitrary",)


def test_key_bias_blocks_are_legal_for_the_lowering():
    """A key bias rides ``(1, 1, bk)`` blocks of ``[B, 1, Tk]``: bk is a
    multiple of 128 lanes or the whole row, in the dispatcher's pick, the
    autotuner's candidates and the decode route alike."""
    from deeplearning4j_tpu.ops import autotune as at
    assert fa.pick_kv_block(1024, has_bias=True) == 128
    assert fa.pick_kv_block(64, has_bias=True) == 64      # whole row
    assert fa.pick_kv_block(192, has_bias=True) is None   # 96 is neither
    assert fa.pick_kv_block(192) == 96
    for tk in (128, 512, 1024):
        cands = at.candidates(128, tk, 64, 2, has_bias=True)
        assert cands and all(bk % 128 == 0 or bk == tk for _, bk in cands)
    assert not at._valid_blocks([1, 64], 1, 1024, 64, np.float32,
                                decode=True, has_bias=True)
    assert at._valid_blocks([1, 128], 1, 1024, 64, np.float32,
                            decode=True, has_bias=True)

"""The ``qwen3_next_80b_a3b.pretrain.s32k`` cell's own files (configuration,
program, reference, traffic, entry, readers) driven through
``run_cell.run`` at a tiny size on the CPU, as ``test_smallthinker_cell.py``
drives its cell; the catalog row's numbers in the configuration;
``flops_gdn``'s count at the published widths against the figures the
configuration was chosen on; the reference's loss against the program's at
a tiny size; and the three new readers on traces made by hand.

Run by hand: ``JAX_PLATFORMS=cpu python -m pytest
benchmarks/tests/test_qwen3_next_cell.py -q``.
"""

import copy
import json
import os
import time
from types import SimpleNamespace as NS

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.harness import events, flops, flops_gdn, flops_lm, run_cell
from benchmarks.harness import trace as tr
from benchmarks.harness.cell import BENCH_DIR, ROOT, Cell
from benchmarks.metrics import (delta_rule_roofline, gdn_train_mfu_pct,
                                linear_attn_time_pct)

LIMITS = {"loss_gap": 1e-4, "opt_medgap_s2": 1e-2, "delta_medgap_s2": 1e-2,
          "opt_diff_s2": 5e-2, "delta_diff_s2": 5e-2}
NAME = "qwen3_next_80b_a3b.pretrain.s32k"

#: the catalog row's ``config`` (Qwen Qwen3-Next-80B-A3B-Instruct): every
#: number of it is in the file under its own key, but those the cut changes
CATALOG = {"decoder_sparse_step": 1, "full_attention_interval": 4,
           "head_dim": 256, "hidden_act": "silu", "hidden_size": 2048,
           "intermediate_size": 5120, "linear_conv_kernel_dim": 4,
           "linear_key_head_dim": 128, "linear_num_key_heads": 16,
           "linear_num_value_heads": 32, "linear_value_head_dim": 128,
           "max_position_embeddings": 262144, "mlp_only_layers": [],
           "model_type": "qwen3_next", "moe_intermediate_size": 512,
           "norm_topk_prob": True, "num_attention_heads": 16,
           "num_experts": 512, "num_experts_per_tok": 10,
           "num_hidden_layers": 48, "num_key_value_heads": 2,
           "partial_rotary_factor": 0.25, "rms_norm_eps": 1e-06,
           "rope_scaling": None, "rope_theta": 10000000,
           "shared_expert_intermediate_size": 512,
           "tie_word_embeddings": False, "use_sliding_window": False,
           "vocab_size": 151936}


def _load(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def tiny_cell(limits=None):
    cfg = _load(BENCH_DIR, "configs", "qwen3_next_80b_a3b.json")
    cfg.update(hidden_size=32, num_attention_heads=4, num_key_value_heads=2,
               head_dim=16, linear_num_key_heads=2, linear_num_value_heads=4,
               linear_key_head_dim=8, linear_value_head_dim=8,
               moe_intermediate_size=16, shared_expert_intermediate_size=16,
               num_experts=4, num_experts_per_tok=3, vocab_size=48,
               compute_dtype="float32")
    cfg["input"] = dict(cfg["input"], vocab_size=48)
    cfg["deployment"] = dict(cfg["deployment"], num_experts_routed=16,
                             held=[0, 4])
    cfg["assumed"] = dict(cfg["assumed"], initializer_std=0.1)
    t = _load(BENCH_DIR, "traffic", "pretrain.s32k.json")
    t.update(seq_len=80, batches=2, follow_steps=2, snapshots=[2])
    return Cell(NAME, _load(ROOT, "BENCHMARK.json"), 1, copy.deepcopy(cfg),
                t, limits or {})


def test_qwen3_next_cell_stages():
    import deeplearning4j_tpu  # noqa: F401
    cell = tiny_cell(LIMITS)
    result = run_cell.run(cell, 2 ** 31 + 47, 0.3, False, jax.devices()[:1],
                          time.perf_counter(), events.CompileEvents())
    assert result["device"]["platform"] == "cpu" and result["metrics"] == {}
    assert result["attempted"] > 0 and result["failed"] == 0
    assert result["compiled_in_window"] == 0
    assert result["correct"], result["compared"]
    json.dumps(result)


def test_the_cell_is_declared_and_finds_its_files():
    cell = Cell.load(NAME)
    assert cell.reference() and cell.program() and cell.entry()
    assert cell.chips == 1 and cell.limits
    assert {"gdn_train_mfu_pct", "linear_attn_time_pct",
            "delta_rule_roofline", "attn_time_pct", "flash_time_pct",
            "moe_load_max_over_mean", "device_idle_pct", "peak_hbm_pct",
            "input_exposed_pct", "step_host_exposed_pct",
            "idle_unattributed_pct", "fwd_time_pct", "bwd_time_pct",
            "recompute_time_pct", "updater_time_pct",
            "scope_unattributed_pct"} == set(cell.metric_names("per_layer"))
    cfg = cell.config
    reduced = set(cfg["reduced"])
    assert reduced == {"num_hidden_layers", "num_experts", "vocab_size"}
    for key, value in CATALOG.items():
        if key not in reduced:
            assert cfg[key] == value, key
    # the cut: one period, 16 of 512 experts, an eighth of the vocabulary,
    # with the published counts beside them
    assert (cfg["num_hidden_layers"], cfg["num_experts"],
            cfg["vocab_size"]) == (4, 16, 18992)
    assert cfg["published"] == {"num_hidden_layers": 48, "num_experts": 512,
                                "vocab_size": 151936}
    dep = cfg["deployment"]
    assert (dep["chips_per_layer"], dep["num_experts_routed"], dep["held"],
            dep["vocab_slices"], dep["absent"]) == (32, 512, [0, 16], 8,
                                                    "stand_in")
    assert cell.traffic["batch"] * cell.traffic["seq_len"] == 32768
    assert cell.traffic["batches"] == cell.traffic["follow_steps"] == 4
    # the parameters the configuration states: 424,340,544
    leaves = cell.reference().layer_table(cfg)
    assert sum(int(np.prod(s)) for _, s, _ in leaves) == 424_340_544
    assert "424,340,544" in cfg["cut"]["parameters"]


def test_the_count_of_operations():
    """A token's forward at 32,768 positions, by part, as the configuration
    was chosen on: a Gated DeltaNet layer's projections 67.4M, its chunked
    recurrence 5.24M (4 C dk + C (dk + dv) + 6 dk dv + 2 C dv a value head
    at chunk 64), its experts 10.4M; the full layer's projections 54.5M and
    causal scores 268.4M; the head 77.8M: 660M a token, 65 TFLOP a step."""
    cfg = _load(BENCH_DIR, "configs", "qwen3_next_80b_a3b.json")
    T = 32768
    lin, full = (flops_gdn.layer_forward_flops(cfg, i, T) for i in (0, 3))
    assert lin["projections"] / T == 2 * 2048 * (12288 + 64 + 4096)
    assert flops_gdn.recurrence_flops_per_token(cfg) == 32 * (
        4 * 64 * 128 + 64 * 256 + 6 * 128 * 128 + 2 * 64 * 128)
    assert round(lin["recurrence"] / T / 1e6, 2) == 5.24
    moe = sum(lin[k] for k in ("router", "shared", "experts", "shared_gate"))
    assert round(moe / T / 1e6, 1) == 10.4
    assert round(full["projections"] / T / 1e6, 1) == 54.5
    assert round(full["scores"] / T / 1e6, 1) == 268.4
    assert full["scores"] == 4.0 * 16 * 256 * flops_lm.open_pairs(T)
    head = 2.0 * (T - 1) * 2048 * 18992
    total = flops_gdn.forward_flops(cfg, T)
    assert round(head / T / 1e6, 1) == 77.8
    assert 655e6 < total / T < 665e6
    step = flops_gdn.train_flops_per_example(cfg, {"seq_len": T})
    assert 64e12 < step < 66e12
    # the three linear layers carry 38% of the FLOPs, the full layer 50%
    linear = sum(sum(flops_gdn.layer_forward_flops(cfg, i, T).values())
                 for i in range(3))
    assert 0.37 < linear / total < 0.39
    assert 0.49 < sum(full.values()) / total < 0.51
    ctx = {"peaks": {"flops_bf16": 1.0}, "config": cfg,
           "traffic": {"seq_len": T}, "chips": 1,
           "window": {"examples": 1, "seconds": 1.0}}
    assert gdn_train_mfu_pct.read(ctx)["value"] == 100.0 * step
    assert gdn_train_mfu_pct.read(dict(ctx, config={"hidden_size": 1})) \
        is None


def test_the_references_loss_is_the_programs():
    cell = tiny_cell()
    cfg, ref = cell.config, cell.reference()
    w = ref.init_weights(5, cfg)
    ids = np.random.default_rng(8).integers(0, 48, (2, 80), dtype=np.int32)
    net = cell.program().build(cfg, w, {"seq_len": 80})
    y = np.ones((2, 1), np.float32)
    with jax.default_matmul_precision("highest"):
        got, _ = net._build_loss_fn()(net.params, net.state, None,
                                      (jnp.asarray(ids),), (jnp.asarray(y),),
                                      (None,), (None,))
        want = ref.loss(w, (ids, y), cfg, "float32")
    assert abs(float(got) - float(want)) <= 1e-5 * float(want)


def test_linear_attn_time_pct_reads_the_linear_scopes_alone():
    att = {"busy": 1000, "scope": {"attn.linear": 300,
                                   "attn.linear.project": 150,
                                   "attn.full": 200, "moe.experts": 100}}
    assert linear_attn_time_pct.read({"scopes": att}) == {"value": 45.0,
                                                          "unit": "%"}
    att = {"busy": 1000, "scope": {"attn.full": 200}}
    assert linear_attn_time_pct.read({"scopes": att}) is None
    assert linear_attn_time_pct.read({"scopes": None}) is None


def _kernel(name, result, operands):
    ops = ", ".join(f"{o}{{2,1,0}} %p{i}" for i, o in enumerate(operands))
    return (f"%{name} = {result}{{2,1,0}} custom-call({ops}), "
            'custom_call_target="tpu_custom_call"')


ROWS = "bf16[4,32768,128]"
P = "bf16[4,32768,64]"
LAST = "f32[1,4,512]"
STATES = "f32[4,512,128,128]"
DELTA = {
    "delta_rule_fwd.3": _kernel("delta_rule_fwd.3", ROWS,
                                [ROWS] * 4 + [P, LAST]),
    "delta_rule_states.1": _kernel("delta_rule_states.1", STATES,
                                   [ROWS] * 3 + [LAST]),
    "delta_rule_bwd.2": _kernel(
        "delta_rule_bwd.2",
        f"({ROWS}{{2,1,0}}, {ROWS}{{2,1,0}}, {ROWS}{{2,1,0}}, "
        f"{ROWS}{{2,1,0}}, {P}{{2,1,0}}, f32[4,32768,1])",
        [ROWS] * 4 + [P, LAST, STATES, ROWS]),
}
OTHER = _kernel("causal_flash_fwd.1", "bf16[32,32768,256]",
                ["bf16[32,32768,256]"] * 3)


def _trace(durations):
    evs, t = [], 0
    for name, ns in durations:
        evs.append(NS(name=name, start_ns=t, duration_ns=ns, stats=[]))
        t += ns
    dev = NS(name="/device:TPU:0", lines=[NS(name="XLA Ops", events=evs)])
    host = NS(name="/host:CPU", lines=[NS(name="python", events=[
        NS(name=tr.WINDOW_SPAN, start_ns=0, duration_ns=t, stats=[])])])
    return tr.Reduced(NS(planes=[dev, host]))


def test_delta_rule_roofline_finds_the_kernels_by_name():
    """Each kernel's least time from its shapes: the forward reads five
    ``[heads, positions, width]`` arrays and the chunks' last decays and
    writes one; every event here takes 4x its least time, so the share is
    25%, and the attention kernel beside them is not read."""
    peaks = {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9}
    costs = {n: delta_rule_roofline.cost(line) for n, line in DELTA.items()}
    rows, T = 4, 32768
    assert costs["delta_rule_fwd.3"][0] == rows * T * (
        6 * 128 * 128 + 2 * 64 * 128)
    assert costs["delta_rule_states.1"][0] == rows * T * 4 * 128 * 128
    assert costs["delta_rule_bwd.2"][0] == rows * T * (
        14 * 128 * 128 + 4 * 64 * 128)
    fwd_bytes = 5 * rows * T * 128 * 2 + rows * T * 64 * 2 + 4 * 512 * 4
    assert costs["delta_rule_fwd.3"][1] == fwd_bytes
    assert delta_rule_roofline.cost(OTHER) is None
    least = {n: flops.roofline_seconds(*c, peaks)[0] for n, c in costs.items()}
    spans = [(DELTA[n], int(4e9 * s)) for n, s in least.items()]
    got = delta_rule_roofline.read({"peaks": peaks, "trace": _trace(
        spans + [(OTHER, 10 ** 6)])})
    assert got["unit"] == "%" and abs(got["value"] - 25.0) < 1e-3
    assert 0 < got["value"] <= 100
    # the parent's program, which has no such kernel, leaves the metric out
    assert delta_rule_roofline.read(
        {"peaks": peaks, "trace": _trace([(OTHER, 10 ** 6)])}) is None

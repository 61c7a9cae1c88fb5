"""The program's side of the ``kanana2_30b_a3b`` configuration: lay the stack
out with the package's builder, tell every sparse layer which experts this
chip holds, hand the graph the benchmark's weights, read its state back
under the reference's leaf names (``<vertex>/<param>``).

The reference keeps each router's selection bias among its weights (its
gradient is exactly zero there); the program keeps it in the layer's state,
outside what the updater sweeps. ``build`` puts it there, ``params`` reads it
back beside the parameters and ``first_moment`` reports the zeros an
optimizer that never saw it would hold, so the two sides' trees match."""

from __future__ import annotations

BIAS = "select_bias"


def build(cfg: dict, weights: dict, traffic: dict):
    from deeplearning4j_tpu.models.decoder_stack import VERTICES_PER_LAYER
    from deeplearning4j_tpu.models.kanana import kanana2
    from deeplearning4j_tpu.nn.updaters import Adam
    upd = cfg["assumed"]["updater"]
    if upd["kind"] != "adam":
        raise ValueError("kanana2_30b_a3b is configured for Adam")
    dtype = {"bfloat16": "BFLOAT16", "float32": "FLOAT"}[cfg["compute_dtype"]]
    deployment = cfg["deployment"]
    # the builder reads the model's own keys: the router's width is the
    # published count, the experts held are this chip's
    model = dict(cfg, n_routed_experts=deployment["num_experts_routed"])
    net = kanana2(model, traffic["seq_len"], held=tuple(deployment["held"]),
                  updater=Adam(learning_rate=upd["learning_rate"],
                               beta1=upd["beta1"], beta2=upd["beta2"],
                               epsilon=upd["epsilon"]),
                  dtype=dtype,
                  workspace_mode=f"every_{VERTICES_PER_LAYER}").init()
    nested, biases = {}, {}
    for name, value in weights.items():
        vertex, param = name.split("/")
        if param == BIAS:
            biases[vertex] = value
        else:
            nested.setdefault(vertex, {})[param] = value
    shapes = lambda tree: {k: {p: v.shape for p, v in leaves.items()}
                           for k, leaves in tree.items() if leaves}
    if shapes(net.params) != shapes(nested) or \
            {k: v.shape for k, v in biases.items()} != \
            {k: s[BIAS].shape for k, s in net.state.items() if BIAS in s}:
        raise ValueError("the reference's leaves are not the program's")
    net.params = {k: (nested[k] if v else v) for k, v in net.params.items()}
    net.state = {k: (dict(s, **{BIAS: biases[k]}) if k in biases else s)
                 for k, s in net.state.items()}
    return net


def _flat(tree: dict) -> dict:
    return {f"{vertex}/{param}": value for vertex, leaves in tree.items()
            for param, value in leaves.items()}


def _biases(net) -> dict:
    return {f"{vertex}/{BIAS}": s[BIAS] for vertex, s in net.state.items()
            if BIAS in s}


def params(net) -> dict:
    return {**_flat(net.params), **_biases(net)}


def first_moment(net) -> dict:
    import jax.numpy as jnp
    return {**_flat(net.updater_state["m"]),
            **{k: jnp.zeros_like(v) for k, v in _biases(net).items()}}

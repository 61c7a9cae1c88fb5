"""The program's side of the ``keye_vl2_30b_a3b`` configuration: lay the stack
out with the package's builder, tell every sparse layer which experts this
chip holds, hand the graph the benchmark's weights, read its state back under
the reference's leaf names (``<vertex>/<param>``). The indexer's three
matrices are parameters on both sides; no gradient reaches them on either."""

from __future__ import annotations


def build(cfg: dict, weights: dict, traffic: dict):
    from deeplearning4j_tpu.models.decoder_stack import VERTICES_PER_LAYER
    from deeplearning4j_tpu.models.keye import keye_vl2
    from deeplearning4j_tpu.nn.updaters import Adam
    upd = cfg["assumed"]["updater"]
    if upd["kind"] != "adam":
        raise ValueError("keye_vl2_30b_a3b is configured for Adam")
    dtype = {"bfloat16": "BFLOAT16", "float32": "FLOAT"}[cfg["compute_dtype"]]
    deployment = cfg["deployment"]
    # the builder reads the model's own keys: the router's width is the
    # published count, the experts held are this chip's
    model = dict(cfg, num_experts=deployment["num_experts_routed"])
    net = keye_vl2(model, traffic["seq_len"], held=tuple(deployment["held"]),
                   updater=Adam(learning_rate=upd["learning_rate"],
                                beta1=upd["beta1"], beta2=upd["beta2"],
                                epsilon=upd["epsilon"]),
                   dtype=dtype,
                   workspace_mode=f"every_{VERTICES_PER_LAYER}").init()
    nested = {}
    for name, value in weights.items():
        vertex, param = name.split("/")
        nested.setdefault(vertex, {})[param] = value
    have = {k: {p: v.shape for p, v in leaves.items()}
            for k, leaves in net.params.items() if leaves}
    want = {k: {p: v.shape for p, v in leaves.items()}
            for k, leaves in nested.items()}
    if have != want:
        raise ValueError("the reference's leaves are not the program's")
    net.params = {k: (nested[k] if v else v) for k, v in net.params.items()}
    return net


def _flat(tree: dict) -> dict:
    return {f"{vertex}/{param}": value for vertex, leaves in tree.items()
            for param, value in leaves.items()}


def params(net) -> dict:
    return _flat(net.params)


def first_moment(net) -> dict:
    return _flat(net.updater_state["m"])

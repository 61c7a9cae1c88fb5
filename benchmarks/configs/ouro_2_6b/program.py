"""The program's side of the ``ouro_2_6b`` configuration: lay the looped
stack out with the package's builder (the layers and the final norm one
repeated run of the graph, every weight one leaf), hand the graph the
benchmark's weights, read its state back under the reference's leaf names
(``<vertex>/<param>``)."""

from __future__ import annotations


def build(cfg: dict, weights: dict, traffic: dict):
    from deeplearning4j_tpu.models.decoder_stack import vertices_per_layer
    from deeplearning4j_tpu.models.ouro import ouro
    from deeplearning4j_tpu.nn.updaters import Adam
    assumed = cfg["assumed"]
    upd = assumed["updater"]
    if upd["kind"] != "adam":
        raise ValueError("ouro_2_6b is configured for Adam")
    dtype = {"bfloat16": "BFLOAT16", "float32": "FLOAT"}[cfg["compute_dtype"]]
    net = ouro(cfg, traffic["seq_len"], exit_beta=assumed["exit_beta"],
               updater=Adam(learning_rate=upd["learning_rate"],
                            beta1=upd["beta1"], beta2=upd["beta2"],
                            epsilon=upd["epsilon"]),
               dtype=dtype,
               workspace_mode=f"every_{vertices_per_layer(True)}").init()
    nested = {}
    for name, value in weights.items():
        vertex, param = name.split("/")
        nested.setdefault(vertex, {})[param] = value
    shapes = lambda tree: {k: {p: v.shape for p, v in leaves.items()}
                           for k, leaves in tree.items()}
    if shapes(net.params) != shapes(nested):
        raise ValueError("the reference's leaves are not the program's")
    net.params = nested
    return net


def _flat(tree: dict) -> dict:
    return {f"{vertex}/{param}": value for vertex, leaves in tree.items()
            for param, value in leaves.items()}


def params(net) -> dict:
    return _flat(net.params)


def first_moment(net) -> dict:
    return _flat(net.updater_state["m"])

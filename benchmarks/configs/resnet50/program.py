"""The program's side of the ``resnet50`` configuration: build the DL4J zoo
graph the normal way, hand it the benchmark's weights, read its state back
under the reference's leaf names (``<layer>/<param>``)."""

from __future__ import annotations


def build(cfg: dict, weights: dict, traffic: dict):
    from deeplearning4j_tpu.models.resnet import resnet50
    from deeplearning4j_tpu.nn.updaters import Nesterovs
    upd = cfg["assumed"]["updater"]
    if upd["kind"] != "nesterovs":
        raise ValueError("resnet50 is configured for Nesterov momentum")
    dtype = {"bfloat16": "BFLOAT16", "float32": "FLOAT"}[cfg["compute_dtype"]]
    size = cfg["image_size"]
    net = resnet50(num_classes=cfg["num_classes"],
                   input_shape=(size, size, cfg["channels"]),
                   updater=Nesterovs(learning_rate=upd["learning_rate"],
                                     momentum=upd["momentum"]),
                   seed=0, dtype=dtype).init()
    nested = {}
    for name, value in weights.items():
        layer, param = name.split("/")
        nested.setdefault(layer, {})[param] = value
    have = {k: set(v) for k, v in net.params.items() if v}
    if have != {k: set(v) for k, v in nested.items()}:
        raise ValueError("the reference's leaves are not the program's")
    for layer, leaves in nested.items():
        for param, value in leaves.items():
            if net.params[layer][param].shape != value.shape:
                raise ValueError(f"{layer}/{param}: shape "
                                 f"{value.shape} != program's")
    net.params = {k: (nested[k] if v else v) for k, v in net.params.items()}
    return net


def _flat(tree: dict) -> dict:
    return {f"{layer}/{param}": value for layer, leaves in tree.items()
            for param, value in leaves.items()}


def params(net) -> dict:
    return _flat(net.params)


def first_moment(net) -> dict:
    return _flat(net.updater_state["v"])

"""The system under test for ``resnet50``: the program's own ResNet-50
(``paddle_tpu.models.image.resnet`` under ``dsl.classification_cost``)
in a ``Trainer``, and the map from the reference's leaf names to the
program's.  Nothing of the program's numerics is redone here."""

from __future__ import annotations


def build(sizes, optimizer, chips):
    from paddle_tpu.config import dsl
    from paddle_tpu.config.model_config import OptimizationConfig
    from paddle_tpu.data.feeder import dense_vector, integer_value
    from paddle_tpu.layers.network import NeuralNetwork
    from paddle_tpu.models.image import resnet
    from paddle_tpu.trainer.trainer import Trainer

    px, n_cls = int(sizes["image_size"]), int(sizes["num_classes"])
    with dsl.config_scope():
        img = dsl.data("image", dense_vector(3 * px * px),
                       height=px, width=px)
        lab = dsl.data("label", integer_value(n_cls))
        probs = resnet(img, depth=int(sizes["depth"]), num_classes=n_cls)
        cfg = dsl.topology(dsl.classification_cost(probs, lab))
    trainer = Trainer(NeuralNetwork(cfg), opt_config=OptimizationConfig(
        learning_method=optimizer["method"],
        momentum=float(optimizer["momentum"]),
        learning_rate=float(optimizer["lr"]),
        l2_weight_decay=float(optimizer.get("l2", 0.0))), seed=0)
    return trainer, _leaf_map(cfg)


def _leaf_map(cfg):
    """reference leaf → program leaf.  The DSL numbers its layers in the
    order ``models/image.py`` builds them (per block: shortcut, then the
    three convs, each conv followed by its batch norm), which is the
    order of ``reference/resnet50.py::conv_plan``."""
    from chipbench import harness as H

    ref = H.load_module("reference", "resnet50")
    convs = [l.name for l in cfg.layers if l.type == "exconv"]
    bns = [l.name for l in cfg.layers if l.type == "batch_norm"]
    fcs = [l.name for l in cfg.layers if l.type == "fc"]
    plan = ref.conv_plan(None)
    if not (len(convs) == len(bns) == len(plan) and len(fcs) == 1):
        raise RuntimeError("the program's ResNet does not have the "
                           "reference's layers")
    out = {}
    for (name, *_), conv, bn in zip(plan, convs, bns):
        out[name + ".w"] = f"_{conv}.w0"
        out[name + ".b"] = f"_{conv}.wbias"
        out[name + ".g"] = f"_{bn}.w0"
        out[name + ".beta"] = f"_{bn}.wbias"
    out["fc.w"] = f"_{fcs[0]}.w0"
    out["fc.b"] = f"_{fcs[0]}.wbias"
    return out

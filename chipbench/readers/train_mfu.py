"""The whole step's share of the chips' peak: model FLOPs per item
(``flops/<config>.py``, forward + backward, nothing recomputed) x items
per second / (chips x peak bf16 FLOP/s), in percent."""

from chipbench import harness as H


def read(run):
    cell, ctx = run["cell"], run["ctx"]
    flops = H.load_module("flops", cell.config_name, ctx["here"])
    per_item = flops.train_flops_per_item(cell.config["sizes"],
                                          cell.traffic)
    rate = run["work"] / run["window_s"]
    return 100.0 * per_item * rate / (
        ctx["chips"] * ctx["peaks"]["bf16_flops_per_s"])

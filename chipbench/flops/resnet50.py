"""Model FLOPs of ResNet-50 from its shapes (He et al. 2015, table 1):
multiply-accumulates of every convolution and of the classifier, times
2 for FLOPs, times 3 for forward + backward (the backward pass is two
products per forward product; nothing recomputed counts)."""

from __future__ import annotations

STAGES = (3, 4, 6, 3)


def conv_shapes(sizes):
    """(k, cin, cout, out_side) of every convolution."""
    side = int(sizes["image_size"]) // 2            # stem 7x7 stride 2
    out = [(7, 3, 64, side)]
    side //= 2                                      # 3x3 max pool stride 2
    cin = 64
    for si, blocks in enumerate(STAGES):
        ch = 64 * 2 ** si
        for bi in range(blocks):
            stride = 2 if si > 0 and bi == 0 else 1
            side //= stride                         # the first 1x1 strides
            if cin != ch * 4 or stride != 1:
                out.append((1, cin, ch * 4, side))
            out.append((1, cin, ch, side))
            out.append((3, ch, ch, side))
            out.append((1, ch, ch * 4, side))
            cin = ch * 4
    return out


def forward_macs(sizes) -> float:
    macs = sum(k * k * cin * cout * side * side
               for k, cin, cout, side in conv_shapes(sizes))
    return float(macs + 2048 * int(sizes["num_classes"]))


def train_flops_per_item(sizes, traffic=None) -> float:
    """FLOPs one image costs in a train step."""
    return 3.0 * 2.0 * forward_macs(sizes)

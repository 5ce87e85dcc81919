"""ResNet-18 (He et al. 2016, arXiv:1512.03385, Table 1), as the
benchmark's reference builds it.

7x7/2 stem, 3x3/2 max-pool, four stages of two basic blocks (64, 128,
256, 512 channels; the first block of stages 2-4 strides by 2 and takes
a 1x1/2 projection shortcut), global average pool, 1000-way fully
connected layer.  There is no batch normalisation: in the int8
fake-quant network each conv's output is requantized by its own shift.
Node names number every conv, add and pool in order, as the served
graph's builder does, since the seeded weights are keyed by name.
"""
from reference import NetBuilder

STAGES = ((2, 64), (2, 128), (2, 256), (2, 512))


def build(in_hw: int = 224, n_classes: int = 1000) -> dict:
    b = NetBuilder("resnet18", (3, in_hw, in_hw))
    i = 0

    def conv(t, cout, k, stride, pad, relu=True):
        nonlocal i
        i += 1
        t = b.conv(f"conv{i}", t, cout, k, stride, pad)
        return b.relu(f"relu{i}", t) if relu else t

    t = conv("input", 64, 7, 2, 3)
    i += 1
    t = b.maxpool(f"pool{i}", t, 3, 2, 1)
    cin = 64
    for stage, (blocks, cout) in enumerate(STAGES):
        for blk in range(blocks):
            stride = 2 if stage > 0 and blk == 0 else 1
            y = conv(t, cout, 3, stride, 1)
            y = conv(y, cout, 3, 1, 1, relu=False)
            sc = conv(t, cout, 1, stride, 0, relu=False) \
                if stride != 1 or cin != cout else t
            i += 1
            t = b.relu(f"relu{i}", b.add(f"add{i}", y, sc))
            cin = cout
    t = b.gap("gap", t)
    t = b.flatten("flatten", t, "flat.out")
    return b.net(b.fc("fc", t, n_classes))

"""A two-conv CNN at 8x8, for the benchmark's own CPU tests only:
conv3x3(4) - relu - conv3x3(8) - relu - maxpool2 - fc(10)."""
from reference import NetBuilder


def build(in_hw: int = 8, c1: int = 4, c2: int = 8, n_classes: int = 10) -> dict:
    b = NetBuilder("tiny_cnn", (3, in_hw, in_hw))
    t = b.relu("relu1", b.conv("conv1", "input", c1, 3, 1, 1))
    t = b.relu("relu2", b.conv("conv2", t, c2, 3, 1, 1))
    t = b.maxpool("pool", t, 2, 2, 0)
    t = b.flatten("flatten", t, "flat.out")
    return b.net(b.fc("fc", t, n_classes))

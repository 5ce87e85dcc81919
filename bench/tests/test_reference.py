"""The reference's semantics are pinned: ResNet-18's and ``tiny_cnn``'s
weights, outputs and shifts hash to the digests recorded before a network
could bring its own ops, and their work counts are unchanged.  A
network's own crossbar op is given weights, counted, calibrated and
degraded by the control as ``conv`` and ``fc`` are."""
import hashlib
import importlib
import json

import numpy as np
import pytest

import reference
import work

ISAAC = {"act_bits": 8, "weight_bits": 8, "dac_bits": 1, "cell_bits": 2,
         "parallel_row": 8, "adc_bits": 8}
# a crossbar whose 6-bit ADC saturates: the bit-sliced path of ``cim_mvm``
SATURATING = dict(ISAAC, parallel_row=128, adc_bits=6)
SEED = 2 ** 31 + 11


def _net(name, **sizes):
    return importlib.import_module(f"nets.{name}").build(**sizes)


def _digest(net, cim, seed=SEED):
    """sha256 of the seeded weights, the calibration pass's output, an
    inference at the calibrated shifts, the same at int4 operands, and
    the shifts."""
    w = reference.make_weights(net, seed)
    y0, shifts = reference.forward(net, w,
                                   reference.calibration_input(net, seed), cim)
    x = np.random.default_rng([seed, 5]).integers(-128, 128,
                                                  net["input_shape"])
    y1, _ = reference.forward(net, w, x, cim, shifts=shifts)
    y4, _ = reference.forward(net, w, x, cim, shifts=shifts, operand_bits=4)
    h = hashlib.sha256()
    for k in sorted(w):
        h.update(k.encode())
        h.update(np.ascontiguousarray(w[k], np.int64).tobytes())
    for y in (y0, y1, y4):
        h.update(np.ascontiguousarray(y, np.int64).tobytes())
    h.update(json.dumps(shifts, sort_keys=True).encode())
    return h.hexdigest()


@pytest.mark.parametrize("name, sizes, cim, digest", [
    ("resnet18", {"in_hw": 32}, ISAAC,
     "3de574d14b19799f01142fcda70c8291e485d1cff1151922b3832769e62988d6"),
    ("resnet18", {"in_hw": 32}, SATURATING,
     "ead7de5148ef112a5ed354e2836b8aa7b1cef0e5de496bb5ceab5e094f619e89"),
    ("tiny_cnn", {}, ISAAC,
     "e12ed7fd8080b219e5f2723db1a73397dd9f27eb1d25f146e16434b96d795c12"),
    ("tiny_cnn", {}, SATURATING,
     "d877690fa5dedced45086b061910116398666ac4c8a1d9f7f0ad5f8125bdcd7e"),
], ids=["resnet18-isaac", "resnet18-saturating", "tiny_cnn-isaac",
        "tiny_cnn-saturating"])
def test_outputs_and_shifts_are_the_recorded_ones(name, sizes, cim, digest):
    assert _digest(_net(name, **sizes), cim) == digest


@pytest.mark.parametrize("name, sizes, macs", [
    ("resnet18", {"in_hw": 32}, 37_523_456),
    ("resnet18", {}, 1_814_073_344),
    ("tiny_cnn", {}, 26_624),
], ids=["resnet18-32", "resnet18-224", "tiny_cnn"])
def test_work_counts_are_the_recorded_ones(name, sizes, macs):
    assert work.macs_per_inference(_net(name, **sizes)) == macs


def test_a_networks_own_crossbar_op_is_weighted_counted_and_shifted():
    net = _net("tiny_mlp")
    assert net["ops"] and {n["op"] for n in reference.cim_nodes(net)} == \
        {"gemm"}
    w = reference.make_weights(net, SEED)
    assert {k: v.shape for k, v in w.items()} == {"fc1": (16, 32),
                                                   "fc2": (32, 8)}
    assert work.macs_per_inference(net) == 16 * 32 + 32 * 8
    y, shifts = reference.forward(net, w,
                                  reference.calibration_input(net, SEED),
                                  ISAAC)
    assert set(shifts) == {"fc1", "fc2"} and min(shifts.values()) > 0
    assert y.shape == (8,) and np.abs(y).max() <= 128
    x = np.random.default_rng(1).integers(-128, 128, net["input_shape"])
    y8, _ = reference.forward(net, w, x, ISAAC, shifts=shifts)
    y4, _ = reference.forward(net, w, x, ISAAC, shifts=shifts,
                              operand_bits=4)
    assert not np.array_equal(y8, y4)


def test_a_node_states_its_own_macs():
    b = reference.NetBuilder("attn", (4, 8))
    t = b.node("qk", "matmul", ["input", "input"], (4, 4), macs=4 * 4 * 8)
    assert work.macs_per_inference(b.net(t)) == 128
    assert reference.cim_nodes(b.net(t)) == []


def test_an_op_defined_nowhere_raises():
    b = reference.NetBuilder("odd", (4,))
    net = b.net(b.node("sm", "softmax", ["input"], (4,)),
                ops={"gemm": lambda node, xs, ctx: xs[0]})
    with pytest.raises(ValueError, match="no op 'softmax'"):
        reference.forward(net, {}, np.zeros(4, np.int64), ISAAC)


@pytest.mark.parametrize("op", reference.BUILTIN_OPS)
def test_a_network_op_may_not_shadow_the_references(op):
    """A network's ``fc`` would otherwise never be called: ``forward``
    would run the reference's own."""
    b = reference.NetBuilder("shadow", (4,))
    t = b.node("x", op, ["input"], (4,))
    with pytest.raises(ValueError, match="shadow"):
        b.net(t, ops={op: lambda node, xs, ctx: xs[0]})

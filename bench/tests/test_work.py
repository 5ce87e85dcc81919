"""Work counts and the reference's network against the served graph."""
import importlib

import pytest

import reference
import work
from repro.core.graph import weight_matrix_shape
from repro.workloads import get_workload


def _net(name, **sizes):
    return importlib.import_module(f"nets.{name}").build(**sizes)


def test_resnet18_macs_at_224():
    macs = work.macs_per_inference(_net("resnet18"))
    assert macs == 1_814_073_344          # about 1.82 G, He et al. Table 1


def test_unknown_device_has_no_peaks():
    with pytest.raises(KeyError):
        work.peaks("cpu")


@pytest.mark.parametrize("name,sizes", [("resnet18", {"in_hw": 224}),
                                        ("tiny_cnn", {}), ("tiny_mlp", {})])
def test_reference_net_mirrors_served_graph(name, sizes):
    net, graph = _net(name, **sizes), get_workload(name, **sizes)
    ours = [(n["name"], n["R"], n["C"]) for n in reference.cim_nodes(net)]
    theirs = [(n.name, *weight_matrix_shape(n)) for n in graph.cim_nodes]
    assert ours == theirs
    assert [n["name"] for n in net["nodes"]] == [n.name for n in graph.nodes]
    for n in net["nodes"]:
        assert tuple(net["shapes"][n["output"]]) == \
            tuple(graph.shapes[n["output"]])

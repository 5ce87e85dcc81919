"""The DeepSeek-V2-Lite cell at tiny widths on the CPU: a whole run of
the harness is correct, its int4 control is not, a traced run reads the
routed-rows counter, and the network's work counts its routed share."""
import json

import pytest

import run
import work

CELL = "deepseek-v2-lite-isaac16.prefill1k"
WIDTHS = dict(hidden=64, heads=4, qk_nope=16, qk_rope=8, v_head=16,
              kv_lora=32, d_ff=96, moe_d_ff=32, n_experts=8, top_k=2,
              n_shared=2)


def _cell():
    _, cfg, mix, e2e, per_layer = run.load_cell(CELL)
    cfg = dict(cfg, arch="isaac-baseline",
               sizes=dict(tokens=32, dense_layers=1, moe_layers=1,
                          experts_held=4, vocab=64, widths=WIDTHS))
    return cfg, dict(mix, pool=16), e2e, per_layer


@pytest.mark.parametrize("trace,control_bits", [(False, 8), (True, 8),
                                                (False, 4)],
                         ids=["run", "traced", "int4-control"])
def test_tiny_run(trace, control_bits):
    cfg, mix, e2e, per_layer = _cell()
    out = run.run_cell(cfg, mix, e2e, per_layer, seed=2 ** 31 + 9,
                       seconds=1.0, trace=trace, require_tpu=False,
                       control_bits=control_bits)
    assert out["correct"] is (control_bits == 8)
    assert out["failed"] == 0
    names = set(out["metrics"])
    if trace:
        assert "expert_load.prefill1k" in names
        assert out["metrics"]["expert_load.prefill1k"]["value"] >= 1.0
    elif control_bits == 8:
        assert {"inferences_per_s", "setup_s"} <= names


def test_published_work_and_weights():
    _, cfg, _, _, _ = run.load_cell(CELL)
    net = run.load_net(cfg)
    crossbar = [n for n in net["nodes"] if "R" in n]
    assert sum(n["R"] * n["C"] for n in crossbar) == 508_821_504
    routed = [n for n in crossbar if "route" in n]
    assert len(routed) == 4 * 8 * 3
    for n in routed:            # tokens x 6/64 rows per held expert
        assert n["macs"] == round(1024 * 6 / 64 * n["R"] * n["C"])
    assert 250e9 < work.macs_per_inference(net) < 252e9
    assert json.loads(json.dumps(cfg))["check_requests"] == 4

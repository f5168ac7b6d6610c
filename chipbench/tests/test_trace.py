"""The trace reduction, on a hand-made trace and on a recorded one."""
import json

import pytest

from chipbench import harness
from chipbench.trace import Trace, load_compact
from conftest import DATA, ROOT

HAND = {"planes": [
    {"name": "/device:TPU:0", "lines": [
        {"name": "XLA Ops", "events": [["a", 0, 10], ["b", 5, 10],
                                       ["c", 30, 5]]},
        {"name": "XLA Modules", "events": [["jit_f(1)", 0, 15],
                                           ["jit_g(2)", 30, 5]]}]},
    {"name": "/host:CPU", "lines": [
        {"name": "python", "events": [["cb.sweep", 0, 40],
                                      ["cb.step", 16, 10],
                                      ["PjitFunction(f)", 1, 1]]}]}]}


def test_hand_trace():
    t = Trace(HAND)
    assert t.window_ns == 40
    assert t.busy_ns() == 20 and t.idle_share() == 0.5
    assert t.busy_ns(10, 32) == 7
    assert [s.name for s in t.spans] == ["cb.sweep", "cb.step"]
    assert [e.name for e in t.device_events("jit_f")] == ["jit_f(1)"]
    assert [e.name for e in t.device_events("a", line="XLA Ops")] == ["a"]
    assert t.top_ops(2) == [["a", 1e-8], ["b", 1e-8]]
    gaps = t.idle_gaps()
    assert [g[0] for g in gaps] == ["cb.step", "cb.sweep"]
    assert [g[1] for g in gaps] == pytest.approx([15e-9, 5e-9])


def test_hand_trace_price_metrics():
    t = Trace(HAND)
    run = harness.Run(cell=None, counters={}, trace=t, peaks={})
    mod = harness.load_module(
        ROOT / "chipbench/metrics/host_ms_per_sweep.price.py", "hms")
    assert mod.read(run) == pytest.approx(20e-6)     # 40 ns - 20 ns busy
    idle = harness.load_module(
        ROOT / "chipbench/metrics/idle_share.price.py", "idle")
    assert idle.read(run) == 50.0
    assert idle.read(harness.Run(None, {}, None, {})) is None


def test_recorded_v5e_price_trace():
    """A trace recorded on a TPU v5e (two sweeps of the pricing cell):
    two calls of the fused bracket kernel, 340 ms each, in a 3.53 s
    window."""
    from chipbench.drivers.price import record_bundles
    from repro.core import compile_bundle
    t = Trace(load_compact(DATA / "price_trace_v5e.json.gz"))
    assert t.devices == ["/device:TPU:0"]
    evs = t.device_events("bracket", line="XLA Ops")
    assert [round(e.dur / 1e6) for e in evs] == [340, 340]
    assert t.window_ns == pytest.approx(3.5274e9, rel=1e-4)
    assert t.idle_share() == pytest.approx(0.8062, abs=1e-4)
    assert t.top_ops(1)[0][0] == "%fused_bracket_segsum.1"

    cfg = json.loads((ROOT / "chipbench/configs/miniapps-paper.json")
                     .read_text())
    cbs = [compile_bundle(b) for b in record_bundles(cfg)]
    counters = {"scenarios_per_sweep": 262144,
                "n_calls": sum(cb.n_calls for cb in cbs)}
    for g in ("hit", "lfb", "miss"):
        counters[f"n_{g}"] = sum(len(getattr(cb, g + "_lat")) for cb in cbs)
    peaks = json.loads((ROOT / "chipbench/peaks.json").read_text())
    run = harness.Run(cell=None, counters=counters, trace=t,
                      peaks=peaks["devices"]["TPU v5 lite"])
    roof = harness.load_module(
        ROOT / "chipbench/metrics/bracket_roofline.price.py", "roof")
    share = roof.read(run)
    # bound by bytes: four (262144, 56) float32 outputs and the inputs
    # at 819 GB/s against 340 ms a call
    from chipbench.costs.bracket import bracket_bytes
    want = bracket_bytes(262144, counters["n_hit"], counters["n_lfb"],
                         counters["n_miss"], counters["n_calls"]) / 819e9
    assert share == pytest.approx(100 * 2 * want / 0.6798716, rel=1e-5)
    assert 0 < share < 100


def test_serve_metrics_on_a_hand_trace():
    """Each serve reader finds its programs by jit name and its steps by
    the traced interval; 10 ms of decode with the weights alone to read
    reads as their bytes over 819 GB/s over 10 ms."""
    cfg = json.loads((ROOT / "chipbench/configs/qwen2.5-3b.json")
                     .read_text())
    cell = harness.Cell("serve", 1, cfg, {}, [], [])
    trace = Trace({"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules", "events": [
                ["jit__decode_slots_paged(1)", 0, 1e7],
                ["jit__prefill_chunk_step(2)", 2e7, 5e6]]},
            {"name": "XLA Ops", "events": [["%while.1", 0, 1e7],
                                           ["%while.2", 2e7, 5e6]]}]},
        {"name": "/host:CPU", "lines": [{"name": "python", "events": [
            ["cb.step", 0, 3e7]]}]}]})
    counters = {"trace_t0": 0.0, "trace_t1": 10.0,
                "steps": [(1.0, 2.0, 1, 0)], "admit_wait_s": [0.1, 0.3],
                "prompt_tokens": 10, "output_tokens": 10, "ctx_sum": 0,
                "t0": 0.0, "drain_end": 1.0}
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    run = harness.Run(cell, counters, trace, peaks)

    def read(name):
        return harness.load_module(
            ROOT / f"chipbench/metrics/{name}.py", name).read(run)

    assert read("decode_step_ms.serve") == pytest.approx(10.0)
    assert read("prefill_chunk_ms.serve") == pytest.approx(5.0)
    from chipbench.costs.dense_lm import all_params, matmul_params
    m = cfg["model"]
    assert read("decode_roofline.serve") == pytest.approx(
        100 * 2 * all_params(m) / 819e9 / 1e-2)
    assert read("mfu.serve") == pytest.approx(
        100 * 2 * matmul_params(m) * 20 / 197e12)
    assert read("admit_wait_p95_ms.serve") == pytest.approx(290.0)
    assert read("idle_share.serve") == pytest.approx(100 * 15 / 30)

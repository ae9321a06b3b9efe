"""The reduction from a device trace to numbers: on hand-made events whose
answers are known, and on the small traces recorded on the chip by PR 22
(``perfbench/fixtures/``)."""

import glob
import os

import pytest

from perfbench import harness, trace_reduce as tr

FAMILIES = harness.KERNEL_FAMILIES
MS = 1e6  # ns


def _trace():
    """One device: a 10 ms ``while`` holding a 2 ms decode kernel and a
    3 ms fusion, then 4 ms idle (host in ``serving.tick``), then a 2 ms
    all-reduce and a 1 ms all-gather-done; a second device busy all along."""
    d0 = [["%while.2 = (s32[]) while(...)", 0, 10 * MS],
          ["%fleetx_decode_paged.6 = bf16[16,1,2048] custom-call(...)", 1 * MS, 2 * MS],
          ["%fusion.12 = bf16[8] fusion(...)", 4 * MS, 3 * MS],
          ["%all-reduce.3 = f32[4] all-reduce(...)", 14 * MS, 2 * MS],
          ["%all-gather-done.1 = f32[4] all-gather-done(...)", 16 * MS, 1 * MS],
          ["%fusion.13 = bf16[8] fusion(...)", 17 * MS, 3 * MS]]
    d1 = [["%fusion.12 = bf16[8] fusion(...)", 0, 20 * MS]]
    host = [["serving.tick", 9 * MS, 6 * MS], ["serving.decode", 9.5 * MS, 0.2 * MS]]
    mods = [["jit__decode_fn(123)", 0, 10 * MS], ["jit_prefill(77)", 14 * MS, 6 * MS]]
    return {"devices": {"/device:TPU:0": d0, "/device:TPU:1": d1},
            "modules": {"/device:TPU:0": mods}, "host": host}


def test_busy_union_idle_share_self_time_collectives_and_gaps():
    r = tr.reduce_trace(_trace(), FAMILIES)
    assert r["devices"] == 2
    assert r["window_s"] == pytest.approx(0.020)
    assert r["busy_s"] == pytest.approx((0.016 + 0.020) / 2)  # mean of devices
    assert r["idle_share"] == pytest.approx(4 / 20)           # worst device
    assert r["family_s"]["decode"] == pytest.approx(0.002 / 2)
    assert r["family_calls"] == {"flash": 0, "decode": 1, "ce": 0}
    assert r["collective_exposed_s"] == pytest.approx(0.003)
    ops = dict(r["device_ops"])
    assert ops["while"] == pytest.approx(0.005 / 2)           # self time only
    assert ops["fusion"] == pytest.approx((0.006 + 0.020) / 2)
    assert r["idle_gaps"] == [["serving.tick", pytest.approx(0.004)]]
    assert r["module_s"] == {"jit__decode_fn": [0.010], "jit_prefill": [0.006]}
    assert r["kernel_events"][0][0] == "decode"


def test_names_and_nesting():
    assert tr.instruction("%fusion.12.3 = bf16[8] fusion(...)") == "fusion"
    assert tr.instruction("%fleetx_flash_fwd.4 = ...") == "fleetx_flash_fwd"
    assert tr.is_collective("%all-gather-start.2 = ...")
    assert tr.is_collective("%reduce-scatter.1 = ...")
    assert not tr.is_collective("%fusion.2 = bf16[2] fusion(%all-reduce.1)")
    timed = tr.self_times([["a", 0, 10], ["b", 1, 3], ["c", 2, 1], ["d", 5, 2],
                           ["e", 10, 1]])
    assert [t[3] for t in timed] == [5, 2, 1, 2, 1]
    assert tr.reduce_trace({"devices": {}, "host": []}, FAMILIES) == {}


def test_a_gap_outside_every_span_is_no_span():
    trace = {"devices": {"/device:TPU:0": [["%a.1 = x", 0, MS], ["%a.2 = x", 3 * MS, MS]]},
             "host": [["train.step", 10 * MS, MS]]}
    r = tr.reduce_trace(trace, FAMILIES)
    assert r["idle_gaps"] == [["no span", pytest.approx(0.002)]]
    assert r["idle_share"] == pytest.approx(0.5)


FIXTURES = sorted(glob.glob(os.path.join(harness.HERE, "fixtures", "*.json")))


@pytest.mark.parametrize("path", FIXTURES, ids=os.path.basename)
def test_recorded_trace_reduces_to_consistent_numbers(path):
    assert os.path.getsize(path) < 1_000_000
    trace = tr.load_dump(path)
    r = tr.reduce_trace(trace, FAMILIES)
    assert r["devices"] == len(trace["devices"]) >= 1
    assert 0 < r["busy_s"] <= r["window_s"]
    assert 0 <= r["idle_share"] < 1
    # self times partition the busy time: families + collectives + the rest
    parts = sum(r["family_s"].values()) + r["xla_s"]
    assert parts <= r["busy_s"] * 1.001 + r["collective_exposed_s"]
    assert sum(v for _, v in r["device_ops"]) <= r["busy_s"] * 1.001
    assert any(r["family_calls"].values()), "no fleetx kernel in the trace"
    assert r["idle_gaps"] and all(s >= 0 for _, s in r["idle_gaps"])
    spans = {name for name, _, _ in trace["host"]}
    assert spans & {"serving.tick", "train.step"}
    if len(trace["devices"]) > 1:
        assert r["collective_exposed_s"] > 0


def test_serve_fixture_numbers_as_looked_at_by_hand():
    """``serve_docs_batch_v5e.json``: 259 ms of the docs-batch cell on one
    v5e: two prefill programs and two decode ticks of GPT-1.3B."""
    r = tr.reduce_trace(tr.load_dump(os.path.join(
        harness.HERE, "fixtures", "serve_docs_batch_v5e.json")), FAMILIES)
    assert r["devices"] == 1
    assert r["window_s"] == pytest.approx(0.2592, abs=1e-4)
    assert r["busy_s"] == pytest.approx(0.2279, abs=1e-4)
    assert r["idle_share"] == pytest.approx(0.1207, abs=1e-3)
    assert r["family_calls"]["decode"] == 48          # 2 ticks x 24 layers
    assert r["family_s"]["decode"] == pytest.approx(0.02636, abs=1e-4)
    assert len(r["module_s"]["jit_prefill"]) == 2
    assert len(r["module_s"]["jit__decode_fn"]) == 2
    assert sum(r["module_s"]["jit__decode_fn"]) == pytest.approx(0.1053, abs=1e-3)
    gaps = dict(r["idle_gaps"])
    # the idle time sits in the tick's host work and in admission
    assert gaps["serving.tick"] == pytest.approx(0.02138, abs=1e-4)
    assert gaps["serving.admit"] == pytest.approx(0.00992, abs=1e-4)
    assert r["collective_exposed_s"] == 0.0


def test_train_fixture_numbers_and_flash_call_cost():
    """``train_345m_v5e.json``: 695 ms of the 345M pretrain cell: one whole
    ``jit_train_step`` and the start of the next."""
    from perfbench import flops, peaks
    from perfbench.layer_metrics import flash_attention_roofline as roof

    r = tr.reduce_trace(tr.load_dump(os.path.join(
        harness.HERE, "fixtures", "train_345m_v5e.json")), FAMILIES)
    assert r["family_calls"]["flash"] == 120
    assert r["family_s"]["flash"] == pytest.approx(0.2517, abs=1e-3)
    assert r["idle_share"] == pytest.approx(0.0245, abs=1e-3)
    assert r["module_s"]["jit_train_step"] == [pytest.approx(0.5415, abs=1e-3)]
    assert dict(r["idle_gaps"])["train.callback"] == pytest.approx(0.01298, abs=1e-4)
    # every traced flash call's shapes are read from its own operands:
    # 16 sequences x 16 heads, 1024 x 1024 causal, head size 64
    v5e = peaks.peaks_for("TPU v5 lite")
    kinds = {}
    for family, name, _ in r["kernel_events"]:
        ops, bytes_ = roof.call_cost(name)
        kinds[tr.instruction(name)] = (ops, flops.roofline_seconds(ops, bytes_, v5e)[1])
    pair = 256 * 1024 * 1024 * 0.5 * 64 * 2
    assert kinds == {"fleetx_flash_fwd": (2 * pair, "compute"),
                     "fleetx_flash_dq": (3 * pair, "compute"),
                     "fleetx_flash_dkv": (4 * pair, "compute")}
    with pytest.raises(peaks.UnknownDeviceKind):
        peaks.peaks_for("TPU v9 imaginary")


def test_four_chip_fixture_has_four_devices_and_exposed_collectives():
    """``train_1.3b_dp2mp2_v5e.json``: 397 ms of the four-chip pretrain
    cell (dp2 x mp2): the tensor-parallel all-reduces sit on the critical
    path, 28% of the window on the worst device."""
    r = tr.reduce_trace(tr.load_dump(os.path.join(
        harness.HERE, "fixtures", "train_1.3b_dp2mp2_v5e.json")), FAMILIES)
    assert r["devices"] == 4
    assert r["window_s"] == pytest.approx(0.3970, abs=1e-3)
    assert r["idle_share"] == pytest.approx(0.0080, abs=1e-3)
    assert r["collective_exposed_s"] == pytest.approx(0.1110, abs=1e-3)
    assert dict(r["device_ops"])["all-reduce"] == pytest.approx(0.1110, abs=1e-3)
    assert r["family_calls"]["flash"] == 39
    assert 0.25 < r["collective_exposed_s"] / r["window_s"] < 0.30

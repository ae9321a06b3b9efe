"""Device time by model part (``perfbench/layer_metrics/_parts.py``) and
the readers over it and over the program's leaf spans: the wire-format
reader on a hand-made ``.xplane.pb``, the rules on op_names as the chip
gives them, the partition on the traces recorded on the chip by PR 23
(``perfbench/fixtures/*_parts_v5e.json``), and every new reader on a
traced and on an untraced run."""

import glob
import json
import os

import pytest

from fleetx_tpu.obs.tracing import Span
from perfbench import harness, trace_reduce as tr
from perfbench.layer_metrics import _parts

FIXTURES = os.path.join(harness.HERE, "fixtures")
NEW = [m for m in harness.load_json("BENCHMARK.json")["per_layer"]
       if m["name"].split(".")[-1].endswith(("_device_share", "_host_ms_p50"))
       and m["name"].split(".")[-1] not in (
           "flash_device_share", "decode_kernel_device_share",
           "xla_ops_device_share")]
MS = 1e6  # ns


# ------------------------------------------------------ the wire format

def _varint(n):
    out = b""
    while True:
        out += bytes([(n & 0x7F) | (0x80 if n > 0x7F else 0)])
        n >>= 7
        if not n:
            return out


def _field(number, value):
    if isinstance(value, int):
        return _varint(number << 3) + _varint(value)
    value = value.encode() if isinstance(value, str) else value
    return _varint(number << 3 | 2) + _varint(len(value)) + value


def _map(number, key, message):
    return _field(number, _field(1, key) + _field(2, message))


def _xspace():
    """One device plane with one program of two instructions (the first
    encloses the second), and a host plane that must be skipped."""
    tf_op, program_id = 1, 2
    named = (_field(1, 1) + _field(2, "%while.3 = (s32[]) while(...)")
             + _field(5, _field(1, program_id) + _field(3, 77)))
    scoped = (_field(1, 2) + _field(2, "%fusion.1 = s32[16] fusion(...)")
              + _field(5, _field(1, tf_op)
                       + _field(5, "jit(f)/sampler/argmax:"))
              + _field(5, _field(1, program_id) + _field(3, 77)))
    module = _field(1, 3) + _field(2, "jit_f(77)")
    ops = (_field(2, "XLA Ops") + _field(3, 1000)
           + _field(4, _field(1, 2) + _field(2, 5_000_000)
                    + _field(3, 2_000_000))
           + _field(4, _field(1, 1) + _field(2, 4_000_000)
                    + _field(3, 9_000_000)))
    modules = (_field(2, "XLA Modules") + _field(3, 1000)
               + _field(4, _field(1, 3) + _field(2, 0) + _field(3, 20_000_000)))
    device = (_field(2, "/device:TPU:0") + _field(3, ops) + _field(3, modules)
              + _map(4, 1, named) + _map(4, 2, scoped) + _map(4, 3, module)
              + _map(5, tf_op, _field(1, tf_op) + _field(2, "tf_op"))
              + _map(5, program_id,
                     _field(1, program_id) + _field(2, "program_id")))
    host = _field(2, "/host:CPU") + _field(3, _field(2, "main"))
    return _field(1, host) + _field(1, device)


def test_load_xplane_reads_op_name_and_program_from_event_metadata(tmp_path):
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(_xspace())
    assert _parts.load_xplane(str(path)) == {"/device:TPU:0": [
        ["%while.3 = (s32[]) while(...)", "", "jit_f", 5000.0, 9000.0],
        ["%fusion.1 = s32[16] fusion(...)", "jit(f)/sampler/argmax", "jit_f",
         6000.0, 2000.0]]}


# ------------------------------------------------------------ the rules

_SERVE = "jit(_decode_fn)/cached_forward/GPTForPretraining/gpt/"
_STACK = "gpt._decoder_stack/while/body/"
_TRAIN = "jit(train_step)/jvp(GPTForPretraining)/gpt/"
_BACK = "jit(train_step)/transpose(jvp(GPTForPretraining))/gpt/"


@pytest.mark.parametrize("instruction,op_name,part", [
    # what the compiler makes itself, named after its program's scope
    ("convert", "<cached_forward>", "recast"),
    ("copy", "<cached_forward>", "cache_move"),
    ("copy-done", "<cached_forward>", "cache_move"),
    ("copy", "<optimizer>", "carry"),
    ("convert", "<optimizer>", "recast"),
    ("while", "<cached_forward>", "cache_move"),
    ("while", "<optimizer>", "carry"),
    ("convert", "<>", "unscoped"),
    # the scan's own slices and updates move the cache when it serves ...
    ("bitcast_dynamic-update-slice_fusion",
     _SERVE + _STACK + "dynamic_update_slice", "cache_move"),
    ("dynamic-slice_bitcast_fusion", _SERVE + _STACK + "squeeze", "cache_move"),
    ("copy-done", _SERVE + "gpt._decoder_stack/while", "cache_move"),
    ("fusion", _SERVE + _STACK + "closed_call/layers/layer/attn/"
     "attn._update_cache/attn._update_paged_cache/cache_write/scatter",
     "cache_move"),
    # ... and stack saved activations when it trains
    ("bitcast_dynamic-update-slice_fusion",
     _TRAIN + _STACK + "dynamic_update_slice", "carry"),
    ("fusion", _BACK + "gpt._decoder_stack/broadcast_in_dim", "carry"),
    ("fusion", _BACK + _STACK + "closed_call", "carry"),
    # flax's module path
    ("fusion", _SERVE + _STACK + "closed_call/layers/layer/attn/qkv_proj/"
     "dot_general", "attn"),
    ("fusion", _SERVE + _STACK + "closed_call/layers/layer/attn/"
     "attn._update_cache/attn._update_paged_cache/le", "attn"),
    ("convert_reduce_fusion", _BACK + _STACK + "closed_call/layers/layer/norm1/"
     "reduce_sum", "attn"),
    ("fusion", _TRAIN + _STACK + "closed_call/layers/layer/mlp/up_proj/"
     "dot_general", "mlp"),
    ("fusion", "jit(f)/GPTForPretraining/gpt/layer_3/norm2/mul", "mlp"),
    # the scopes this PR sets, bare and as transforms wrap them
    ("fusion", "jit(prefill)/sampler/top_k", "head"),
    ("fusion", "jit(_decode_fn)/lanes/jit(_where)/select_n", "head"),
    ("fusion", _SERVE + "embed/gather", "head"),
    ("fusion", "jit(prefill)/cached_forward/GPTForPretraining/logits/"
     "bsh,vh->bsv/dot_general", "head"),
    ("fusion", _SERVE + "final_norm/reduce_sum", "head"),
    ("fusion", "jit(train_step)/jvp(loss)/reduce_max", "head"),
    ("fusion", "jit(train_step)/transpose(jvp(loss))/mul", "head"),
    ("fusion", "jit(train_step)/optimizer/mul", "update"),
    ("select_fusion", "jit(train_step)/sentry/select_n", "update"),
    # what no rule names
    ("fusion", "jit(train_step)/add", "unscoped"),
    ("fusion", _SERVE + _STACK + "closed_call/layers/layer/add", "unscoped"),
])
def test_part_of(instruction, op_name, part):
    assert _parts.part_of(instruction, op_name) == part
    assert part in _parts.PARTS


def _devices():
    """One serving device: a tick whose ``while`` encloses a cache update,
    a decode kernel and a matmul, after an unnamed weight cast and pool
    copy; then a collective and something no rule knows."""
    op = _SERVE + _STACK
    rows = [
        ["%convert.11 = bf16[24,8,8] convert(f32[24,8,8] %params)", "", 1 * MS],
        ["%copy.81 = bf16[24,9,16,8] copy(...)", "", 2 * MS],
        ["%while.2 = (s32[]) while(...)", "", 10 * MS],
        ["%bitcast_dynamic-update-slice_fusion = ...",
         op + "dynamic_update_slice", 2 * MS],
        ["%fleetx_decode_paged.6 = bf16[16,1,8] custom-call(...)",
         op + "closed_call/layers/layer/attn/fleetx_decode_paged/pallas_call",
         3 * MS],
        ["%fusion.12 = bf16[8] fusion(...)",
         op + "closed_call/layers/layer/mlp/up_proj/dot_general", 4 * MS],
        ["%all-reduce.3 = f32[4] all-reduce(...)", "", 2 * MS],
        ["%fusion.13 = bf16[8] fusion(...)", "jit(_decode_fn)/mystery/add",
         1 * MS]]
    starts = [0, 1 * MS, 3 * MS, 3 * MS, 5 * MS, 8 * MS, 14 * MS, 17 * MS]
    return {"/device:TPU:0": [[text, name, "jit__decode_fn", start, dur]
                              for (text, name, dur), start in zip(rows, starts)]}


def test_parts_families_and_collectives_partition_self_time():
    out = _parts.self_seconds(_devices())
    seconds = out["seconds"]
    assert seconds["recast"] == pytest.approx(0.001)
    # the while's own millisecond has no op_name: its program's scope
    # names it (``<cached_forward>``), and the scan is the cache's
    assert seconds["cache_move"] == pytest.approx(0.002 + 0.002 + 0.001)
    assert seconds["decode"] == pytest.approx(0.003)
    assert seconds["mlp"] == pytest.approx(0.004)
    assert seconds["collectives"] == pytest.approx(0.002)
    assert seconds["unscoped"] == pytest.approx(0.001)
    assert out["top"]["unscoped"][0][0] == "fusion jit(_decode_fn)/mystery/add"
    assert out["top"]["cache_move"][0][0] == "copy <cached_forward>"
    shares = _parts.shares(_devices())
    assert sum(shares.values()) == pytest.approx(1.0, abs=1e-9)
    assert set(shares) == {*_parts.PARTS, *harness.KERNEL_FAMILIES,
                           "collectives"}
    assert _parts.shares({}) == {}


# ------------------------------------------- the traces from the chip

RECORDED = sorted(glob.glob(os.path.join(FIXTURES, "*_parts_v5e.json")))
# share of device self time by part, as recorded (PR 23, my chip runs):
# 261 ms of docs-batch (two prefill programs, two ticks), 695 ms of the
# 345M step, and 228 ms around the end of a dp2 x mp2 step on four chips
# (the tail of the backward pass, the update, the start of the next step)
PINNED = {
    "serve_docs_batch": {
        "recast": 0.1925, "cache_move": 0.4867, "attn": 0.0932,
        "mlp": 0.0902, "head": 0.0167, "unscoped": 0.0001, "decode": 0.1206},
    "train_345m": {
        "recast": 0.0082, "attn": 0.2495, "mlp": 0.2723, "head": 0.0601,
        "update": 0.0250, "carry": 0.0091, "flash": 0.3757},
    "train_1.3b_dp2mp2": {
        "recast": 0.0187, "attn": 0.1367, "mlp": 0.2070, "head": 0.0827,
        "update": 0.0748, "carry": 0.0740, "unscoped": 0.0057,
        "flash": 0.0669, "collectives": 0.3336},
}


def test_a_fixture_per_family_was_recorded():
    assert [os.path.basename(p) for p in RECORDED] == sorted(
        name + "_parts_v5e.json" for name in PINNED)


@pytest.mark.parametrize("path", RECORDED, ids=os.path.basename)
def test_recorded_trace_partitions_and_keeps_its_shares(path):
    devices = _parts.load_dump(path)
    shares = _parts.shares(devices)
    assert sum(shares.values()) == pytest.approx(1.0, abs=1e-6)
    pinned = PINNED[os.path.basename(path)[:-len("_parts_v5e.json")]]
    for part, share in shares.items():
        assert share == pytest.approx(pinned.get(part, 0.0), abs=5e-4), part
    assert shares["unscoped"] < 0.05
    # the same events as the older reduction sees in the same file: the
    # kernel families and collectives agree with ``reduce_trace``
    old = tr.reduce_trace(tr.load_dump(path), harness.KERNEL_FAMILIES)
    seconds = _parts.self_seconds(devices)["seconds"]
    for family, value in old["family_s"].items():
        assert seconds[family] == pytest.approx(value, rel=1e-6, abs=1e-9)
    others = sum(seconds[p] for p in _parts.PARTS)
    assert others == pytest.approx(old["xla_s"], rel=1e-6)
    with open(path) as f:
        packed = json.load(f)
    assert len(packed["op_names"]) == len(packed["names"]) \
        == len(packed["programs"])


# ----------------------------------------------------------- the readers

def _run(spans=(), trace=None, window=(0.0, 100.0), traced=None):
    return harness.Run(
        cell=None, device={}, setup_s=0.0, window=window, attempted=0,
        failed=0, correct=True, checks={}, samples={}, spans=list(spans),
        counters={}, traced=traced, trace=trace)


def _span(name, start_ms, end_ms, parent=None, **attrs):
    return Span(name=name, start_s=start_ms / 1e3, end_s=end_ms / 1e3,
                thread_id=1, depth=0, attrs=attrs, parent=parent)


def _tick(number, start_ms, fetch_ms=(2.0, 50.0), admit=None):
    """A 52 ms tick: decode dispatched 1.0-1.6 ms in, fetch as given."""
    t = start_ms
    spans = [_span("serving.tick", t, t + 52.0, tick=number),
             _span("serving.decode", t + 1.0, t + 1.6, "serving.tick", batch=16),
             _span("serving.fetch", t + fetch_ms[0], t + fetch_ms[1],
                   "serving.tick", batch=16)]
    if admit is not None:
        spans += [
            _span("serving.admit", t + 0.1, t + 0.9, "serving.tick",
                  request=admit),
            _span("serving.first_token", t + 0.3, t + 0.8, "serving.admit",
                  request=admit)]
    return spans


@pytest.mark.parametrize("metric", NEW, ids=lambda m: m["name"])
def test_new_reader_gives_nothing_without_its_source(metric):
    """An untraced run of the parent program: no trace, and none of the
    new spans."""
    reader = harness.by_name("layer_metrics", metric["name"])
    old_spans = [s for s in _tick(1, 0.0) + _tick(2, 60.0, admit=5)
                 if s.name in ("serving.tick", "serving.decode",
                               "serving.admit")]
    assert reader.read(_run()) is None
    assert reader.read(_run(spans=old_spans)) is None


@pytest.mark.parametrize("metric", NEW, ids=lambda m: m["name"])
def test_new_reader_gives_a_float_on_a_traced_run(metric, monkeypatch):
    leaf = metric["name"].split(".")[-1]
    reader = harness.by_name("layer_metrics", metric["name"])
    if metric["source"] == "program_span":
        run = _run(spans=_tick(1, 0.0) + _tick(2, 60.0) + _tick(3, 120.0, admit=5))
    else:
        assert leaf.endswith("_device_share")
        assert leaf[:-len("_device_share")] in _parts.PARTS
        monkeypatch.setattr(_parts, "traced_shares",
                            lambda run: _parts.shares(_devices()))
        run = _run(trace={"busy_s": 1.0})
    assert isinstance(reader.read(run), float)


def test_tick_host_is_fetch_end_to_next_dispatch_end_of_plain_ticks():
    from perfbench.layer_metrics import tick_host_ms_p50 as reader

    spans = (_tick(1, 0.0) + _tick(2, 53.0) + _tick(3, 106.0, admit=9)
             + _tick(4, 160.0) + _tick(6, 300.0) + _tick(7, 353.0))
    # 1->2: fetch ends at 50.0, the next dispatch at 53.0 + 1.6; 2->3 and
    # 3->4 hold an admission; 4->6 are not consecutive; 6->7 as 1->2
    assert reader.read(_run(spans=spans)) == pytest.approx(4.6)
    # a tick inside the traced stretch is left out
    assert reader.read(_run(spans=spans, traced=(0.040, 0.060))) == \
        pytest.approx(4.6)
    assert reader.read(_run(spans=spans, traced=(0.0, 0.400))) is None


def test_admit_host_is_the_admission_less_its_wait_for_the_first_token():
    from perfbench.layer_metrics import admit_host_ms_p50 as reader

    spans = _tick(1, 0.0, admit=3) + _tick(2, 60.0, admit=4)
    assert reader.read(_run(spans=spans)) == pytest.approx(0.8 - 0.5)


def test_traced_shares_reads_the_trace_the_run_left_on_disk(tmp_path,
                                                           monkeypatch):
    monkeypatch.setattr(harness, "WORK", str(tmp_path))
    assert _parts.traced_shares(_run(trace={"busy_s": 1.0})) == {}  # no file
    where = tmp_path / "trace" / "plugins" / "profile" / "2026"
    where.mkdir(parents=True)
    (where / "host.xplane.pb").write_bytes(_xspace())
    assert _parts.traced_shares(_run()) == {}  # this run was not traced
    shares = _parts.traced_shares(_run(trace={"busy_s": 1.0}))
    assert shares["head"] == pytest.approx(2 / 9)
    assert shares["unscoped"] == pytest.approx(7 / 9)
    assert _parts.read_share(_run(trace={"busy_s": 1.0}), "head") == \
        pytest.approx(2 / 9)

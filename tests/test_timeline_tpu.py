"""`report timeline` on the TPU's form of a trace: a cut of a trace recorded
on a v5e (tests/fixtures/tpu_v5e_timeline_trace.json) is written back out as
an .xplane.pb, so the whole reader runs on it: the wire-format walk, the
/host:metadata plane's HloProto, the `XLA Modules` / `XLA Ops` join, the
scopes, the loop's host spans and the idle gaps."""

import json
import struct
from pathlib import Path

import pytest

from atomo_tpu.obs import timeline as T
from atomo_tpu.utils import tracing

FIXTURE = json.loads(
    (Path(__file__).parent / "fixtures" / "tpu_v5e_timeline_trace.json").read_text()
)


# ------------------------------------------- a protobuf writer, for the test


def _varint(n: int) -> bytes:
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def _field(number: int, value) -> bytes:
    if isinstance(value, int):
        return _varint(number << 3) + _varint(value)
    if isinstance(value, float):
        return _varint(number << 3 | 1) + struct.pack("<d", value)
    data = value.encode() if isinstance(value, str) else value
    return _varint(number << 3 | 2) + _varint(len(data)) + data


def _plane(name, lines=(), event_meta=None, stat_meta=None) -> bytes:
    """XPlane: name=2, lines=3, event_metadata=4, stat_metadata=5; XLine:
    name=2, timestamp_ns=3, events=4; XEvent: metadata_id=1, offset_ps=2,
    duration_ps=3, stats=4; X*Metadata: id=1, name=2, stats=5."""
    out = _field(2, name)
    for line_name, events in lines:
        body = _field(2, line_name) + _field(3, 0)
        for meta_id, offset_ps, duration_ps, stats in events:
            ev = _field(1, meta_id) + _field(2, offset_ps) + _field(3, duration_ps)
            for stat_id, value in stats:
                ev += _field(4, _field(1, stat_id) + _field(4, value))
            body += _field(4, ev)
        out += _field(3, body)
    for meta_id, (meta_name, stats) in (event_meta or {}).items():
        meta = _field(1, meta_id) + _field(2, meta_name) + b"".join(_field(5, st) for st in stats)
        out += _field(4, _field(1, meta_id) + _field(2, meta))
    for stat_id, stat_name in (stat_meta or {}).items():
        out += _field(5, _field(1, stat_id) + _field(2, _field(1, stat_id) + _field(2, stat_name)))
    return out


def _hlo_proto(module: str, op_names: dict) -> bytes:
    """HloProto.hlo_module=1 > computations=3 > instructions=2 with name=1
    and metadata=7 > op_name=2."""
    instructions = b"".join(
        _field(2, _field(1, name) + _field(7, _field(2, op_name)))
        for name, op_name in op_names.items()
    )
    return _field(1, _field(1, module) + _field(3, _field(1, "main") + instructions))


def write_xplane(fixture: dict, path: Path, with_metadata: bool = True) -> None:
    ids: dict = {}

    def meta_id(name):
        return ids.setdefault(name, len(ids) + 1)

    device_lines = [
        (T.TPU_MODULES_LINE, [(meta_id(n), s, d, []) for n, s, d in fixture["modules"]]),
        (T.TPU_OPS_LINE, [(meta_id(n), s, d, []) for n, s, d in fixture["ops"]]),
    ]
    device = _plane(fixture["device_plane"], device_lines, {i: (n, []) for n, i in ids.items()})
    ids = {}
    host_events = [
        (meta_id(n), s, d, [] if step is None else [(1 if n in tracing.PARENT_SPANS else 2, step)])
        for n, step, s, d in fixture["host"]
    ]
    host = _plane(fixture["host_plane"], [(fixture["host_line"], host_events)],
                  {i: (n, []) for n, i in ids.items()}, {1: "step_num", 2: "step"})
    space = _field(1, device) + _field(1, host)
    if with_metadata:
        proto = _field(1, 1) + _field(6, _hlo_proto(fixture["module"], fixture["op_names"]))
        space += _field(1, _plane(
            "/host:metadata", event_meta={fixture["program_id"]: (fixture["module"], [proto])},
            stat_meta={1: "Hlo Proto"},
        ))
    path.mkdir(parents=True, exist_ok=True)
    (path / "v5e.xplane.pb").write_bytes(space)


@pytest.fixture
def v5e_trace(tmp_path):
    write_xplane(FIXTURE, tmp_path / "trace")
    return str(tmp_path / "trace")


# ------------------------------------------------------------------ the tests


def test_fixture_is_the_tpus_form():
    """What was read on the v5e: an op event carries the instruction's text
    and no scope, and the program id is in the module event's name."""
    assert len(FIXTURE["modules"]) == 4
    assert all(name.endswith(f"({FIXTURE['program_id']})") for name, _, _ in FIXTURE["modules"])
    assert all(name.startswith("%") and " = " in name for name, _, _ in FIXTURE["ops"])
    assert not any("op_name" in name or "metadata" in name for name, _, _ in FIXTURE["ops"])
    assert 5 <= len(FIXTURE["op_names"]) <= 20  # a handful of instructions


def test_build_timeline_reads_encode_and_decode_from_the_v5e_fixture(v5e_trace):
    doc = T.build_timeline(v5e_trace)
    assert doc["consistent"], doc["checks"]
    assert doc["module"] == FIXTURE["module"] and doc["n_dispatches"] == 4
    for span in doc["spans"]:
        phases = span["phases"]
        assert phases["encode"]["busy_ms"] > 0 and phases["decode"]["busy_ms"] > 0
        assert phases["forward_backward"]["busy_ms"] > 0 and phases["update"]["busy_ms"] > 0
        assert phases["exchange"]["busy_ms"] == 0  # one chip: nothing is exchanged
        # the while loops are left out: their bodies' ops are events of their own
        busy = span["compute_ms"] + sum(phases[p]["busy_ms"] for p in T.PHASES)
        assert busy <= span["wall_ms"] * 1.001
    # the codec on this tiny model: the encode's eigh calls outweigh the decode's matmuls
    assert doc["spans"][0]["phases"]["encode"]["busy_ms"] > doc["spans"][0]["phases"]["decode"]["busy_ms"]


def test_timeline_lists_host_spans_and_puts_idle_gaps_down_to_them(v5e_trace):
    doc = T.build_timeline(v5e_trace)
    names = {sp["name"] for sp in doc["host_spans"]}
    assert {tracing.BLOCK, tracing.DISPATCH, tracing.FETCH, tracing.FEED_START,
            tracing.STACK, tracing.PUT, tracing.BOUNDARY} <= names
    blocks = [sp for sp in doc["host_spans"] if sp["name"] == tracing.BLOCK]
    assert len({sp["step"] for sp in blocks}) == len(blocks) >= 4  # one identifier an iteration
    idle = doc["idle_by_span_ms"]
    assert set(idle) <= set(T.HOST_SPANS) | {"(no span)"}
    assert abs(sum(idle.values()) - doc["device_idle_ms"]) < 1e-2
    assert 0 < doc["device_idle_ms"] < doc["device_window_ms"]
    # between two blocks of this tiny model the device waits on the host's fetch
    assert max(idle, key=idle.get) == tracing.FETCH
    text = T.summarize_timeline(doc)
    assert "host spans (count x median ms): block 4 x" in text
    assert "device idle" in text and "fetch" in text
    assert "encode" in text and "forward_backward" in text


def test_without_the_metadata_plane_the_timeline_says_so(tmp_path):
    write_xplane(FIXTURE, tmp_path / "bare", with_metadata=False)
    doc = T.build_timeline(str(tmp_path / "bare"))
    assert not doc["consistent"]
    assert [c["name"] for c in doc["checks"] if not c["ok"]] == ["timeline_phases_present"]


@pytest.mark.parametrize("op_name,phase", [
    ("jit(train_superstep)/while/body/closed_call/encode/vmap(jit(eigh))/eigh", "encode"),
    ("jit(train_superstep)/while/body/closed_call/decode/vmap()/dot_general", "decode"),
    ("jit(train_superstep)/while/body/closed_call/update/add", "update"),
    ("forward_backward/transpose(jvp(ResNet))/BasicBlock_0/Conv_1/conv_general_dilated", "forward_backward"),
    ("jit(spmd_step)/forward_backward/jvp(TransformerLM/Block_0/attention)/dot_general", "attention"),
    ("jit(spmd_step)/forward_backward/transpose(jvp(attention))/bhqk,bhkd->bhqd/dot_general", "attention"),
    ("jit(spmd_step)/forward_backward/jvp(TransformerLM)/Block_0/Dense_0/dot_general", "forward_backward"),
    ("jit(step)/decode_mean/dot_general", "decode"),
    ("jit(step)/dynamic_update_slice", "compute"),
])
def test_phase_of_takes_the_innermost_scope_through_autodiffs_brackets(op_name, phase):
    assert T.phase_of(op_name) == phase


def test_idle_by_span_cuts_a_gap_where_spans_open_and_close():
    spans = [
        {"name": "step", "start_us": 0.0, "end_us": 1000.0},
        {"name": "fetch", "start_us": 100.0, "end_us": 400.0},
        {"name": "boundary", "start_us": 400.0, "end_us": 600.0},
        {"name": "dispatch", "start_us": 650.0, "end_us": 900.0},
    ]
    busy = [(0.0, 300.0), (700.0, 1000.0), (305.0, 310.0)]  # a gap of 5 us is no idle stretch
    idle = T.idle_by_span(busy, spans, 0.0, 1000.0)
    assert idle == {"fetch": 90.0, "boundary": 200.0, "step": 50.0, "dispatch": 50.0}

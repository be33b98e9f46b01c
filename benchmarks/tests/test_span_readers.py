"""The readers ``xspan`` (the program's spans on the profiler's clock) and
``scope`` (device seconds by stage name) on hand-made traces with known
answers: plain stand-ins for what ``ProfileData`` gives, and a serialized
XSpace for the event metadata."""

from types import SimpleNamespace as NS

import pytest

from benchmarks import cells, trace_reduce as tr

xspan = cells._module("readers", "xspan")
scope = cells._module("readers", "scope")


def _planes(doc):
    return [
        NS(name=p["name"], lines=[
            NS(name=ln["name"], events=[NS(name=e[0], start_ns=e[1], duration_ns=e[2]) for e in ln["events"]])
            for ln in p["lines"]])
        for p in doc
    ]


# window [1000, 11000); the device runs an op in [2000, 5000) and [6000, 6500)
DOC = [
    {"name": "/host:CPU", "lines": [
        {"name": "harness", "events": [[tr.MARKER, 1000.0, 12000.0]]},
        {"name": "loop", "events": [
            ["ktpu.queue_pop#bid=1#", 1000.0, 1500.0],   # [1000, 2500): 1000 of it idle
            ["ktpu.commit#bid=1#", 5000.0, 2000.0],      # [5000, 7000): idle [5000,6000) + [6500,7000)
            ["ktpu.loop.idle", 7000.0, 9000.0],          # [7000, 16000): crosses the window's close
            ["PjitFunction(f)", 1500.0, 100.0]]},
        {"name": "worker-0", "events": [["ktpu.bind#bid=1,pods=4#", 500.0, 4500.0]]},   # opens before the window
        {"name": "worker-1", "events": [["ktpu.bind#bid=1,pods=4#", 4000.0, 3000.0]]},
    ]},
    {"name": "/device:TPU:0", "lines": [
        {"name": "XLA Modules", "events": [["jit_wave_run(1)", 2000.0, 3000.0], ["jit_usage_checksum(2)", 6000.0, 500.0]]},
        {"name": "XLA Ops", "events": [["%while.1 = () while()", 2000.0, 3000.0], ["%fusion.2 = f32[] fusion()", 2100.0, 1000.0],
                                       ["%copy.3 = f32[] copy()", 6000.0, 500.0]]},
    ]},
]
WINDOW_S = 10000e-9


def _col(doc=DOC):
    return xspan.collect(_planes(doc))


def test_names_lose_their_metadata_tail_and_only_ktpu_spans_are_kept():
    col = _col()
    assert xspan.span_name("ktpu.bind#bid=1,pods=4#") == "ktpu.bind"
    assert set(col["spans"]) == {"ktpu.queue_pop", "ktpu.commit", "ktpu.loop.idle", "ktpu.bind"}
    assert col["marker"] == (1000.0, 12000.0)
    assert xspan.window_of(col, WINDOW_S) == (1000.0, 11000.0)
    assert xspan.window_of(col, None) == (1000.0, 13000.0)  # the marker's own length


@pytest.mark.parametrize("what,spans,seconds", [
    ("sum_s", ["ktpu.bind"], 4000 + 3000),        # [1000,5000) clipped at the open edge + [4000,7000)
    ("union_s", ["ktpu.bind"], 6000),               # [1000, 7000)
    ("after_last_s", ["ktpu.commit"], 11000 - 7000),
    ("sum_s", ["ktpu.loop.idle"], 4000),            # [7000, 11000): clipped at the close
    ("idle_overlap_s", ["ktpu.queue_pop", "ktpu.commit"], 1000 + 1000 + 500),
    ("idle_overlap_s", ["ktpu.loop.idle"], 4000),
])
def test_each_what_on_a_known_trace(what, spans, seconds):
    col = _col()
    w = xspan.window_of(col, WINDOW_S)
    assert xspan.measure(col, w, what, spans) == pytest.approx(seconds * 1e-9)


def test_nothing_to_read_is_none_not_zero():
    col = _col()
    w = xspan.window_of(col, WINDOW_S)
    assert xspan.measure(col, w, "sum_s", ["ktpu.apiserver.POST.bindings"]) is None  # a program without it
    no_device = xspan.collect(_planes(DOC[:1]))
    assert xspan.measure(no_device, w, "idle_overlap_s", ["ktpu.commit"]) is None
    assert xspan.measure(no_device, w, "union_s", ["ktpu.commit"]) == pytest.approx(2000e-9)
    with pytest.raises(ValueError):
        xspan.measure(col, w, "nope", ["ktpu.commit"])


def test_a_trace_without_the_marker_reads_none():
    doc = [{"name": "/host:CPU", "lines": [DOC[0]["lines"][1]]}, DOC[1]]
    col = _col(doc)
    assert col["marker"] is None and xspan.window_of(col, WINDOW_S) is None
    col["window"] = None
    ctx = {"pods_in_window": 4, "_xspan": col}
    assert xspan.read(ctx, {"what": "sum_s", "spans": ["ktpu.commit"]}) is None


def test_read_is_per_kpod_and_the_table_holds_every_span():
    col = _col()
    col["window"] = xspan.window_of(col, WINDOW_S)
    ctx = {"pods_in_window": 500, "_xspan": col}
    assert xspan.read(ctx, {"what": "union_s", "spans": ["ktpu.bind"]}) == pytest.approx(6000e-9 / 0.5)
    assert xspan.read({"pods_in_window": 0, "_xspan": col}, {"what": "union_s", "spans": ["ktpu.bind"]}) is None
    rows = {name: (n, total, uni) for name, n, total, uni in xspan.table(col, col["window"])}
    assert rows["ktpu.bind"] == (2, pytest.approx(7000e-9), pytest.approx(6000e-9))
    assert set(rows) == {"ktpu.queue_pop", "ktpu.commit", "ktpu.loop.idle", "ktpu.bind"}


def test_intersect():
    assert xspan.intersect([(0, 5), (8, 12)], [(3, 9), (11, 20)]) == [(3, 5), (8, 9), (11, 12)]
    assert xspan.intersect([], [(1, 2)]) == []


# ---- scope --------------------------------------------------------------------

XSPACE_TEXT = """
planes {
  name: "/device:TPU:0"
  event_metadata { key: 1 value { id: 1 name: "%fusion.2 = f32[] fusion()" stats { metadata_id: 7 str_value: "jit(f)/ktpu/wave/admission/ktpu/gang/score/add:" } stats { metadata_id: 8 str_value: "gang.py:1" } } }
  event_metadata { key: 2 value { id: 2 name: "%while.1 = () while()" stats { metadata_id: 7 ref_value: 9 } } }
  event_metadata { key: 3 value { id: 3 name: "%helper.9 = s32[] fusion()" stats { metadata_id: 8 str_value: "nowhere" } } }
  stat_metadata { key: 7 value { id: 7 name: "tf_op" } }
  stat_metadata { key: 8 value { id: 8 name: "source" } }
  stat_metadata { key: 9 value { id: 9 name: "jit(f)/ktpu/wave/admission/while:" } }
}
planes {
  name: "/host:CPU"
  event_metadata { key: 1 value { id: 1 name: "host thing" stats { metadata_id: 7 str_value: "ktpu/gang/score" } } }
  stat_metadata { key: 7 value { id: 7 name: "tf_op" } }
}
"""


def test_tf_ops_from_a_serialized_xspace():
    from jax.profiler import ProfileData

    raw = ProfileData.text_proto_to_serialized_xspace(XSPACE_TEXT)
    assert scope.tf_ops(raw) == {
        "%fusion.2 = f32[] fusion()": "jit(f)/ktpu/wave/admission/ktpu/gang/score/add:",
        "%while.1 = () while()": "jit(f)/ktpu/wave/admission/while:",  # a referenced value
    }  # the device planes only; an instruction without the stat is left out


def test_stage_is_the_innermost_scope():
    assert scope.stage_of("jit(f)/ktpu/wave/admission/while/body/ktpu/gang/score/add:") == "ktpu/gang/score"
    assert scope.stage_of("jit(f)/while/body/add:") is None and scope.stage_of(None) is None


def test_an_enclosing_op_counts_its_self_time_only():
    ops = [(0.0, 100.0, "while"), (10.0, 40.0, "a"), (40.0, 90.0, "cond"), (50.0, 60.0, "b"), (200.0, 210.0, "c")]
    assert scope.self_times(ops) == [(20.0, "while"), (30.0, "a"), (40.0, "cond"), (10.0, "b"), (10.0, "c")]


SCOPE_DOC = [DOC[0], {"name": "/device:TPU:0", "lines": [
    {"name": "XLA Modules", "events": [["jit_chain_dispatch(1)", 2000.0, 3000.0], ["jit_usage_checksum(2)", 6000.0, 500.0],
                                       ["jit_chain_dispatch(1)", 20000.0, 1000.0]]},
    {"name": "XLA Ops", "events": [
        ["%helper.9 = s32[] fusion()", 2000.0, 100.0],      # no op_name: goes with the next named op
        ["%gather.4 = s32[] gather()", 2100.0, 400.0],
        ["%while.1 = () while()", 2500.0, 2500.0],          # encloses fusion.2 twice: self 500
        ["%fusion.2 = f32[] fusion()", 2600.0, 1000.0],
        ["%fusion.2 = f32[] fusion()", 3700.0, 1000.0],
        ["%copy.3 = f32[] copy()", 6000.0, 500.0],          # another module: not counted
        ["%fusion.2 = f32[] fusion()", 20000.0, 1000.0],    # outside the window: not counted
    ]}]}]
NAMES = {
    "%gather.4 = s32[] gather()": "jit(chain_dispatch)/ktpu/gang/precompute/gather:",
    "%while.1 = () while()": "jit(chain_dispatch)/jit(wave_schedule)/while:",
    "%fusion.2 = f32[] fusion()": "jit(chain_dispatch)/ktpu/wave/admission/while/body/ktpu/gang/score/add:",
    "%copy.3 = f32[] copy()": "jit(usage_checksum)/copy:",
}


def test_device_seconds_by_stage():
    table = scope.by_stage(_planes(SCOPE_DOC), NAMES, ["chain_dispatch", "wave_run"], (1000.0, 11000.0))
    assert table["ktpu/gang/precompute"] == pytest.approx(500e-9)  # the gather and its helper
    assert table["ktpu/gang/score"] == pytest.approx(2000e-9)
    assert table[scope.UNSCOPED] == pytest.approx(500e-9)  # the while's own time
    assert table["_placed"] == pytest.approx(100e-9)
    assert table["_ops"][("ktpu/gang/precompute", "helper.9")] == pytest.approx(100e-9)
    assert scope.by_stage(_planes(SCOPE_DOC), NAMES, ["resident_run"], (1000.0, 11000.0)) is None
    assert scope.by_stage(_planes(DOC[:1]), NAMES, ["chain_dispatch"], None) is None


def test_scope_metrics_from_the_table():
    table = scope.by_stage(_planes(SCOPE_DOC), NAMES, ["chain_dispatch"], (1000.0, 11000.0))
    ctx = {"pods_in_window": 2000, "_scope:chain_dispatch": table}
    share = scope.read(ctx, {"what": "scoped_share", "modules": ["chain_dispatch"]})
    assert share == pytest.approx(100.0 * 2500 / 3000)
    ms = scope.read(ctx, {"what": "stage_ms_per_kpod", "stage": "ktpu/gang/score", "modules": ["chain_dispatch"]})
    assert ms == pytest.approx(1000.0 * 2000e-9 / 2.0)
    # a stage this run's ops do not carry reads 0, not None: the metric stays on the line
    assert scope.read(ctx, {"what": "stage_ms_per_kpod", "stage": "ktpu/chain/append", "modules": ["chain_dispatch"]}) == 0.0
    assert scope.read({"pods_in_window": 5, "_scope:x": None}, {"what": "scoped_share", "modules": ["x"]}) is None

"""The trace reduction: busy union, idle gaps, self times and the
attribution of idle time to host spans, by hand on a small trace, and on
an excerpt recorded from a traced run on the chip."""
import os
import sys

HERE = os.path.dirname(__file__)
os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.join(HERE, ".."))

import pytest  # noqa: E402

import tracered  # noqa: E402

NS = 1e9


def op(start, end, name="fusion", module="jit_step", dev="0"):
    return {"dev": dev, "name": name, "start": start, "dur": end - start,
            "module": module, "category": "", "source": ""}


def span(name, start, end):
    return {"tid": "python3", "name": name, "start": start, "dur": end - start}


LISTS = {
    "ops": [op(0, 10 * NS), op(5 * NS, 20 * NS), op(30 * NS, 40 * NS, "dot", "jit_decode_micro")],
    "modules": [{"dev": "0", "name": "jit_step", "start": 0.0, "dur": 20 * NS},
                {"dev": "0", "name": "jit_decode_micro", "start": 30 * NS, "dur": 10 * NS}],
    "host": [span("service.step", 0, 45 * NS), span("engine.iteration", 0, 22 * NS),
             span("engine.sync", 12 * NS, 22 * NS), span("decode.accounting_sync", 41 * NS, 44 * NS)],
}


def test_busy_union_and_idle_gaps_by_hand():
    red = tracered.Reduced.from_lists(LISTS, 0.0, 50 * NS)
    assert red.busy(red.devices[0]) == [(0.0, 20 * NS), (30 * NS, 40 * NS)]
    assert red.busy_s() == pytest.approx(30.0)
    assert red.window_s == pytest.approx(50.0)
    assert red.idle_gaps() == [(20 * NS, 30 * NS), (40 * NS, 50 * NS)]


def test_idle_gaps_go_to_the_innermost_open_span():
    red = tracered.Reduced.from_lists(LISTS, 0.0, 50 * NS)
    gaps = dict(red.longest_gaps())
    # 20-30 s: mid 25, only service.step open; 40-50 s: mid 45 is past
    # every span, so the host was outside the program
    assert gaps == {"service.step": pytest.approx(10.0), "host": pytest.approx(10.0)}
    assert red.innermost_span(42 * NS) == "decode.accounting_sync"
    assert red.innermost_span(15 * NS) == "engine.sync"


def test_window_clips_what_it_holds():
    red = tracered.Reduced.from_lists(LISTS, 8 * NS, 35 * NS)
    assert red.busy_s() == pytest.approx(12.0 + 5.0)
    assert red.module_time(lambda n: n == "jit_step") == (pytest.approx(20.0), 1)
    assert red.top_ops(1)[0][0] == "jit_step/fusion"


def test_self_time_subtracts_nested_spans_once():
    spans = [("service.step", 0.0, 100.0), ("engine.iteration", 10.0, 60.0),
             ("engine.micro_step", 20.0, 30.0), ("decode.bind", 50.0, 70.0)]
    # children cover 10-70 (the micro-step inside the iteration counts once)
    assert tracered.self_times(spans, "service.step") == [40.0]
    assert tracered.self_times(spans, "engine.iteration") == [40.0]


def test_tracer_spans_pair_begin_and_end_per_track():
    ev = [("B", "service.step", 0.0, 1, None), ("B", "engine.iteration", 1.0, 2, None),
          ("E", "engine.iteration", 5.0, 2, None), ("E", "service.step", 9.0, 1, None)]
    assert tracered.tracer_spans(ev) == [("service.step", 0.0, 9.0),
                                         ("engine.iteration", 1.0, 5.0)]


def test_a_recorded_excerpt_reduces_consistently():
    import json

    with open(os.path.join(HERE, "fixtures", "trace_excerpt.json")) as f:
        lists = json.load(f)
    tracered._attribute(lists["ops"], lists["modules"])
    t0 = min(o["start"] for o in lists["ops"])
    red = tracered.Reduced.from_lists(lists, t0, t0 + 1e7)
    busy = red.busy_s()
    idle = sum(e - s for s, e in red.idle_gaps()) / NS
    assert 0 < busy <= red.window_s
    assert busy + idle == pytest.approx(red.window_s)
    assert {o["module"] for o in red.ops} == {"jit_step"}
    # a layer scan's `while` holds the operations inside it: not a leaf
    whiles = [o for o in red.ops if o["name"].startswith("%while")]
    assert whiles and not any(o["leaf"] for o in whiles)
    kernels = red.op_time(tracered.is_pallas)
    assert 0 < kernels < busy
    assert red.top_ops(3)[0][0].startswith("jit_step/")
    assert red.innermost_span(lists["host"][0]["start"] + 1) == lists["host"][0]["name"]

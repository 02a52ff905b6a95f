"""The training step's named parts in the device trace: the phase rules,
kernels matched by name, the per-step divisor, and the loader's fill span
inside the prefetch span, by hand on small lists and on an excerpt recorded
from a traced run on the chip."""
import os
import sys

HERE = os.path.dirname(__file__)
os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.join(HERE, ".."))

import pytest  # noqa: E402

import devtime  # noqa: E402
import harness  # noqa: E402
import tracered  # noqa: E402

MS = 1e6
FWD = "jit(step)/jvp(train.forward)"
BWD = "jit(step)/transpose(jvp(train.forward))"
KERNEL = 'custom-call(...), custom_call_target="tpu_custom_call"'


def op(start, end, instr, module="jit_step", pallas=False):
    name = f"%{instr} = bf16[8] " + (KERNEL if pallas else "fusion()")
    return {"dev": "0", "name": name, "start": start * MS, "dur": (end - start) * MS,
            "module": module}


def span(name, start, end):
    return {"tid": "python3", "name": name, "start": start * MS, "dur": (end - start) * MS}


# two steps of one program: a forward layer loop, a backward layer loop
# (with a recomputed forward kernel), the optimizer, and operations the
# compiler left without an op_name inside and outside the loops; the
# trace names each operation by its instruction, the compiled program
# gives each instruction's op_name
def _step(t):
    return [
        op(t, t + 40, "while.1"),
        op(t, t + 10, "packed_attention_fwd.3", pallas=True),
        op(t + 10, t + 20, "subtract_convert_fusion.8"),
        op(t + 20, t + 30, "grouped_lora_fwd.64", pallas=True),
        op(t + 30, t + 40, "fusion.7"),
        op(t + 40, t + 80, "while.2"),
        op(t + 40, t + 45, "packed_attention_fwd.4", pallas=True),
        op(t + 45, t + 55, "packed_attention_dq.10", pallas=True),
        op(t + 55, t + 65, "packed_attention_dkv.10", pallas=True),
        op(t + 65, t + 70, "transpose_jvp_grouped_lora_bwd__.1", pallas=True),
        op(t + 70, t + 80, "copy.212"),
        op(t + 80, t + 90, "add.5"),
        op(t + 90, t + 95, "copy.3"),
    ]


SCOPES = {
    "while.1": f"{FWD}/while",
    "packed_attention_fwd.3": f"{FWD}/while/body/closed_call/packed_attention_fwd/pallas_call",
    "grouped_lora_fwd.64": f"{FWD}/while/body/closed_call/grouped_lora_fwd/pallas_call",
    "fusion.7": f"{FWD}/while/body/closed_call/dot_general",
    "while.2": f"{BWD}/while",
    "packed_attention_fwd.4": f"{BWD}/while/body/closed_call/checkpoint/"
                              "rematted_computation/packed_attention_fwd/pallas_call",
    "packed_attention_dq.10": f"{BWD}/while/body/closed_call/checkpoint/"
                              "packed_attention_dq/pallas_call",
    "packed_attention_dkv.10": f"{BWD}/while/body/closed_call/checkpoint/"
                               "packed_attention_dkv/pallas_call",
    "transpose_jvp_grouped_lora_bwd__.1": "jit(step)/transpose(jvp(grouped_lora_bwd))/pallas_call",
    "add.5": "jit(step)/train.optimizer/add",
}

LISTS = {
    "ops": _step(0) + _step(100) + [
        op(200, 210, "packed_attention_fwd.9", module="jit_decode_micro", pallas=True)],
    "modules": [{"dev": "0", "name": "jit_step", "start": t * MS, "dur": 95 * MS}
                for t in (0, 100)],
    "host": [span("service.step", -5, 97), span("service.step", 97, 198),
             span("engine.prefetch", -4, -1)],
}


def _reduced(lists=LISTS):
    lists = {k: [dict(e) for e in v] for k, v in lists.items()}
    tracered._attribute(lists["ops"], lists["modules"])
    return tracered.Reduced.from_lists(lists, -5 * MS, 198 * MS)


def _read(name, red, scopes=SCOPES):
    return harness.load_metric(name).read({"trace": red, "program_scopes": scopes})


def test_phase_rules():
    assert devtime.phase_of(f"{FWD}/while/body/dot_general") == "forward"
    # the forward a rematerialised layer recomputes runs in the backward
    assert devtime.phase_of(f"{BWD}/while/body/checkpoint/rematted_computation/"
                            "dot_general") == "backward"
    assert devtime.phase_of("jit(step)/train.optimizer/mul") == "optimizer"
    assert devtime.phase_of("jit(step)/add") == ""
    assert devtime.phase_of("") == ""


def test_an_operation_without_op_name_takes_its_holders():
    ops = [op(0, 40, "while.1"), op(10, 20, "subtract_convert_fusion.8"),
           op(50, 60, "copy.3")]
    assert devtime.scopes(ops, SCOPES) == [f"{FWD}/while", f"{FWD}/while", ""]


def test_op_names_come_from_the_compiled_text():
    text = ('  %packed_attention_dq.10 = bf16[8] custom-call(%x), custom_call_target='
            '"tpu_custom_call", metadata={op_name="jit(step)/packed_attention_dq/'
            'pallas_call" stack_frame_id=3}\n  ROOT %add.5 = f32[] add(%a, %b), '
            'metadata={op_name="jit(step)/train.optimizer/add"}\n  %copy.3 = f32[] copy(%a)\n')
    assert devtime.op_names(text) == {
        "packed_attention_dq.10": "jit(step)/packed_attention_dq/pallas_call",
        "add.5": "jit(step)/train.optimizer/add"}


def test_kernel_names_come_out_of_transforms():
    names = ("packed_attention_fwd", "packed_attention_dq", "grouped_lora_bwd")
    kernel = dict(op(0, 1, "packed_attention_dq.10", pallas=True))
    assert devtime.kernel_of(kernel, names) == "packed_attention_dq"
    kernel = op(0, 1, "transpose_jvp_grouped_lora_bwd__.1", pallas=True)
    assert devtime.kernel_of(kernel, names) == "grouped_lora_bwd"
    assert devtime.kernel_of(op(0, 1, "packed_attention_fwd.3"), names) == ""
    assert devtime.kernel_of(op(0, 1, "closed_call.93", pallas=True), names) == ""


def test_the_step_divisor_counts_service_steps_in_the_window():
    red = _reduced()
    assert devtime.traced_steps(red) == 2
    half = tracered.Reduced.from_lists(
        {"ops": red.ops, "modules": red.modules, "host": LISTS["host"]}, -5 * MS, 100 * MS)
    assert devtime.traced_steps(half) == 1


def test_phases_and_kernels_per_step_by_hand():
    red = _reduced()
    # forward: kernel 10 + unscoped fusion in the loop 10 + kernel 10 + 10
    assert _read("model.forward_ms_per_step", red) == pytest.approx(40.0)
    assert _read("model.backward_ms_per_step", red) == pytest.approx(40.0)
    assert _read("train.optimizer_ms_per_step", red) == pytest.approx(10.0)
    # the forward kernel counts in the forward and recomputed in the backward
    assert _read("kernels.packed_attention_fwd_ms_per_step", red) == pytest.approx(15.0)
    assert _read("kernels.packed_attention_dq_ms_per_step", red) == pytest.approx(10.0)
    assert _read("kernels.packed_attention_dkv_ms_per_step", red) == pytest.approx(10.0)
    assert _read("kernels.grouped_lora_ms_per_step", red) == pytest.approx(15.0)
    # the copy outside every scope is in the step, in no phase
    leaves = red.op_time(lambda o: o["module"] == "jit_step") * 1e3 / 2
    assert leaves == pytest.approx(40.0 + 40.0 + 10.0 + 5.0)


def test_a_program_without_names_reads_nothing():
    unnamed = dict(LISTS, ops=[dict(o, name=o["name"].replace("packed_attention_", "closed_call_"))
                               for o in LISTS["ops"]])
    red = _reduced(unnamed)
    for name in ("packed_attention_fwd", "packed_attention_dq", "packed_attention_dkv"):
        assert _read(f"kernels.{name}_ms_per_step", red) is None
    for name in ("model.forward_ms_per_step", "model.backward_ms_per_step",
                 "train.optimizer_ms_per_step"):
        assert _read(name, _reduced(), scopes={}) is None


def test_fill_lies_inside_prefetch():
    class W:
        steps = [None, None]

    spans = [("service.step", 0.0, 100.0), ("engine.prefetch", 1.0, 30.0),
             ("engine.prefetch.fill", 2.0, 22.0), ("engine.prefetch.h2d", 22.0, 29.0),
             ("service.step", 100.0, 200.0), ("engine.prefetch", 101.0, 126.0),
             ("engine.prefetch.fill", 101.0, 119.0), ("engine.prefetch.h2d", 119.0, 125.0)]
    ctx = {"spans": spans, "window": W()}
    fill = harness.load_metric("data.fill_ms_per_step").read(ctx)
    prefetch = harness.load_metric("data.prefetch_ms_per_step").read(ctx)
    assert fill == pytest.approx((20.0 + 18.0) / 2 / 1e3)
    assert 0 < fill <= prefetch
    # a program without the fill span reads nothing, not 0
    ctx["spans"] = [s for s in spans if s[0] != "engine.prefetch.fill"]
    assert harness.load_metric("data.fill_ms_per_step").read(ctx) is None


def test_a_recorded_excerpt_reads_every_part():
    import json

    with open(os.path.join(HERE, "fixtures", "trace_excerpt_named.json")) as f:
        fx = json.load(f)
    tracered._attribute(fx["ops"], fx["modules"])
    step = [h for h in fx["host"] if h["name"] == "service.step"][0]
    red = tracered.Reduced.from_lists(fx, step["start"], step["start"] + step["dur"])
    assert devtime.traced_steps(red) == 1
    read = {n: _read(n, red, fx["program_scopes"]) for n in (
        "model.forward_ms_per_step", "model.backward_ms_per_step",
        "train.optimizer_ms_per_step", "kernels.packed_attention_fwd_ms_per_step",
        "kernels.packed_attention_dq_ms_per_step", "kernels.packed_attention_dkv_ms_per_step",
        "kernels.grouped_lora_ms_per_step")}
    assert all(v is not None and v > 0 for v in read.values()), read
    leaves = red.op_time(lambda o: o["module"] == "jit_step") * 1e3
    phases = sum(read[f"{p}_ms_per_step"] for p in ("model.forward", "model.backward",
                                                     "train.optimizer"))
    assert phases <= leaves + 1e-9
    # every kernel of the step carries one of the names its metrics read
    pallas = red.op_time(lambda o: o["module"] == "jit_step" and tracered.is_pallas(o)) * 1e3
    kernels = sum(v for n, v in read.items() if n.startswith("kernels."))
    assert kernels == pytest.approx(pallas)
    # the loader's fill lies inside the step's prefetch span
    spans = [(h["name"], h["start"] / 1e3, (h["start"] + h["dur"]) / 1e3) for h in fx["host"]]

    class W:
        steps = [None]

    ctx = {"spans": spans, "window": W()}
    fill = harness.load_metric("data.fill_ms_per_step").read(ctx)
    assert 0 < fill <= harness.load_metric("data.prefetch_ms_per_step").read(ctx)

"""Device time of the training step's named parts, per traced step: the
Pallas kernels by the name each ``pallas_call`` gives, and the phases by
the JAX name stack (HLO op_name) of each operation.

The profiler's ``XLA Ops`` events carry the HLO instruction's text as
their name and no op_name: a named kernel is ``%packed_attention_dq.10 =
...`` (inside a transform, ``%transpose_jvp_grouped_lora_bwd__.1``).  The
op_name of every instruction is in the compiled program, so the phases
join the trace to the live service's compiled training steps by
instruction name:

* forward: under ``train.forward`` and not under ``transpose(``;
* backward: under ``transpose(`` (JAX's name for the backward pass); the
  forward a rematerialised layer recomputes inside the backward carries it
  too, so it counts as backward;
* optimizer: under ``train.optimizer``.

An operation without an op_name (a fusion whose root the compiler added,
a layout copy inside a layer loop) takes that of the innermost operation
holding it, such as the layer scan's ``while``.  A kernel's time counts
wherever it runs: the forward kernel recomputed in the backward is still
that kernel.  Each reading is divided by the service steps the traced
window holds.
"""
from __future__ import annotations

import re
from typing import Dict, List, Optional

from tracered import is_pallas

STEP = "jit_step"
FORWARD = "train.forward"
OPTIMIZER = "train.optimizer"
BACKWARD = "transpose("
_OP_NAME = re.compile(r'^\s*(?:ROOT )?%([\w.\-]+) = .*?op_name="([^"]*)"', re.M)


def instruction(op: Dict) -> str:
    """The HLO instruction's name: ``%packed_attention_dq.10 = ...`` ->
    ``packed_attention_dq.10``."""
    return op["name"].split(" ", 1)[0].lstrip("%")


def op_names(hlo_text: str) -> Dict[str, str]:
    """Instruction name -> op_name of a compiled program's HLO text."""
    return {m.group(1): m.group(2) for m in _OP_NAME.finditer(hlo_text)}


def program_scopes(ctx: Dict) -> Dict[str, str]:
    """Instruction name -> op_name over the live service's compiled
    training steps, one per hTask, lowered again with the arguments the
    engine passes so that compiling finds the executable the window ran.
    Kept in ``ctx`` for the readers that follow."""
    if "program_scopes" not in ctx:
        import jax
        import numpy as np

        from repro.launch.steps import device_put_batch

        svc = ctx["run"].svc
        eng = svc.engine
        n_acc = max(len(eng.plan.tasks), sum(eng.reg.mta.kind_capacity.values()))
        acc = (jax.device_put(np.float32(0.0)),
               jax.device_put(np.zeros((n_acc,), np.float32)))
        out: Dict[str, str] = {}
        for h in range(len(eng.plan.htasks)):
            step = eng._steps[eng.step_signature(h)]
            batch = device_put_batch(next(svc._loaders[h]))
            text = step.lower(eng.backbone, eng.reg.adapter_params, eng.reg.opt_state,
                              eng._slot_steps, batch, eng._member_ids[h],
                              acc).compile().as_text()
            for k, v in op_names(text).items():
                out.setdefault(k, v)
        ctx["program_scopes"] = out
    return ctx["program_scopes"]


def traced_steps(red) -> int:
    """The ``service.step`` host spans that lie inside the traced window."""
    return sum(1 for h in red.host if h["name"] == "service.step"
               and h["start"] >= red.t0 and h["start"] + h["dur"] <= red.t1)


def scopes(ops: List[Dict], by_name: Dict[str, str]) -> List[str]:
    """Each operation's op_name, or where it has none, that of the
    innermost operation holding it (same device)."""
    order = sorted(range(len(ops)), key=lambda i: (ops[i]["dev"], ops[i]["start"],
                                                  -ops[i]["dur"]))
    out = [""] * len(ops)
    stack: List[int] = []
    for i in order:
        o = ops[i]
        while stack and (ops[stack[-1]]["dev"] != o["dev"] or
                         ops[stack[-1]]["start"] + ops[stack[-1]]["dur"] <= o["start"]):
            stack.pop()
        own = by_name.get(instruction(o), "")
        out[i] = own or (out[stack[-1]] if stack else "")
        stack.append(i)
    return out


def _per_step_ms(red, total_ns: float) -> Optional[float]:
    n = traced_steps(red)
    if not n or total_ns <= 0:
        return None
    return total_ns / n / 1e6


def phase_of(scope: str) -> str:
    """``forward``, ``backward``, ``optimizer`` or ``""`` for an op_name."""
    if BACKWARD in scope:
        return "backward"
    if FORWARD in scope:
        return "forward"
    if OPTIMIZER in scope:
        return "optimizer"
    return ""


def phase_ms_per_step(ctx: Dict, phase: str) -> Optional[float]:
    """Leaf device time of one phase of the training step, ms per step."""
    red = ctx["trace"]
    sc = scopes(red.ops, program_scopes(ctx))
    return _per_step_ms(red, sum(o["dur"] for o, s in zip(red.ops, sc)
                                 if o["module"] == STEP and o.get("leaf", True)
                                 and phase_of(s) == phase))


def kernel_of(op: Dict, names) -> str:
    """Which of ``names`` a Pallas operation's instruction carries, alone
    or inside a transform's name (``transpose_jvp_grouped_lora_bwd__.1``);
    ``""`` for any other operation."""
    if not is_pallas(op):
        return ""
    base = re.sub(r"(\.\d+)+$", "", instruction(op))
    for n in names:
        if re.search(r"(?:^|_)%s(?:_|$)" % re.escape(n), base):
            return n
    return ""


def kernel_ms_per_step(red, *names: str) -> Optional[float]:
    """Leaf device time of the named kernels inside ``jit_step``, ms per
    step."""
    return _per_step_ms(red, sum(o["dur"] for o in red.ops
                                 if o["module"] == STEP and o.get("leaf", True)
                                 and kernel_of(o, names)))

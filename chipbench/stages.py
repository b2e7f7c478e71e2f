"""Device time by pipeline stage, read from the program's own stage names.

The dataframe operators run each stage of their device work inside
``jax.named_scope(<stage>)`` (``repro.dataframe.STAGES``), so every HLO
instruction the stage lowers to carries the name in its ``op_name`` metadata,
fusions and the bodies of ``while`` loops included.  The timed program's text
(``Context.hlo``, ``compiled.as_text()``) maps each instruction to its stage:
split the op_name on ``/``, drop the last component (the primitive), and take
the innermost remaining component that is a stage.  A fusion that names no
stage of its own takes the one stage that the instructions of its fused
computation name: the TPU compiler leaves some fusions without metadata (the
scatter of ``build_partition_payload`` under 64-bit types), while the
instructions inside keep theirs.  Any other instruction with no metadata or no
stage is :data:`UNATTRIBUTED`.  The trace's innermost device operations (as
``devtrace.op_ns`` sums them) are then summed by stage, per chip.

A program that names no stage (one written before the names) maps every
instruction to :data:`UNATTRIBUTED`, and the readers built on this module
return ``None``.
"""

from __future__ import annotations

import re
from collections import defaultdict

from chipbench import devtrace

try:
    from repro.dataframe import STAGES
except ImportError:  # a program from before the stage names
    STAGES: tuple[str, ...] = ()

UNATTRIBUTED = "unattributed"
INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%?([^\s=]+)\s+=\s")
COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?(\S+)\s.*\{$")
OP_NAME = re.compile(r'metadata=\{[^}]*?op_name="([^"]*)"')
CALLS = re.compile(r"\bcalls=%?([^\s,]+)")


def stage_of(op_name: str) -> str:
    """The innermost stage named in `op_name`, leaving out its last component."""
    for part in reversed(op_name.split("/")[:-1]):
        if part in STAGES:
            return part
    return UNATTRIBUTED


def instruction_stages(hlo: str) -> dict[str, str]:
    """Each instruction of the compiled program's text, by name, to its stage."""
    stages, calls = {}, {}
    named: dict[str, set[str]] = defaultdict(set)  # computation -> stages its instructions name
    computation = None
    for line in hlo.splitlines():
        inst = INSTRUCTION.match(line)
        if inst is None:
            head = COMPUTATION.match(line)
            computation = head.group(1) if head else computation
            continue
        name = inst.group(1)
        op_name = OP_NAME.search(line, inst.end())
        stages[name] = stage_of(op_name.group(1)) if op_name else UNATTRIBUTED
        if stages[name] != UNATTRIBUTED:
            named[computation].add(stages[name])
        elif callee := CALLS.search(line, inst.end()):
            calls[name] = callee.group(1)
    for name, callee in calls.items():
        if len(named[callee]) == 1:
            (stages[name],) = named[callee]
    return stages


def stage_ns(summary: devtrace.Summary, hlo: str) -> dict[str, dict[str, int]]:
    """Device -> stage -> summed duration of its innermost operations in the window."""
    of = instruction_stages(hlo)
    out = {}
    for dev, events in summary.ops.items():
        total: dict[str, int] = defaultdict(int)
        for name, _, s, e, leaf in events:
            if leaf:
                total[of.get(name, UNATTRIBUTED)] += e - s
        out[dev] = dict(total)
    return out


def per_call_ms(ctx: devtrace.Context, stages: tuple[str, ...]) -> float | None:
    """Device time under `stages` per call, in ms, on the chip that spends the
    most; None where the trace holds no operation of theirs (as where the
    program names none of them)."""
    per_chip = [sum(t.get(st, 0) for st in stages)
                for t in stage_ns(ctx.summary, ctx.hlo).values()]
    if not any(per_chip):
        return None
    return max(per_chip) / ctx.summary.calls / 1e6


def collective_wait_ns(summary: devtrace.Summary, pred) -> dict[str, int]:
    """Device -> time spent inside the collectives with pred(name, opcode)
    waiting for the last peer to enter them.

    The k-th event of one instruction on each chip is one instance of the
    collective.  On chip d, instance i waits min(duration(d, i), the latest
    start over the chips - start(d, i)).  This reads the chips' starts on one
    clock, which holds for the chips of one host under one profiler."""
    events: dict[str, dict[str, list]] = defaultdict(lambda: defaultdict(list))
    for dev, evs in summary.ops.items():
        for name, opcode, s, e, leaf in evs:
            if leaf and pred(name, opcode):
                events[name][dev].append((s, e))
    wait = dict.fromkeys(summary.ops, 0)
    for per_dev in events.values():
        for dev in per_dev:
            per_dev[dev].sort()
        for i in range(min(len(v) for v in per_dev.values())):
            last = max(v[i][0] for v in per_dev.values())
            for dev, v in per_dev.items():
                s, e = v[i]
                wait[dev] += min(e - s, last - s)
    return wait if events else {}

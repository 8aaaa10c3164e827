"""Algorithm 1's phases in the device trace of the GAN step.

The fused step (``repro.core.adversarial.make_fused_step``) runs each phase
under a ``jax.named_scope`` (``adversarial.PHASES``: ``d_real``, ``d_fake``,
``g``), and each phase's gradient reduction and optimizer update under a
nested ``update`` scope.  XLA keeps the scope in every instruction's
``metadata={op_name="..."}``, where JAX also marks backward ops
``transpose(jvp(...))``.  A v5e trace names its ops by their HLO text only,
so the op -> op_name map is read from the compiled program's text
(``op_names``) and joined to the trace by instruction name.  Ops that XLA
adds itself carry no op_name; ``inherit`` gives each that of the op it
serves.

``reduce`` splits the step program's op time inside the traced window by
phase and pass (``fwd``, ``bwd``, ``update``), with an ``unscoped``
remainder; op time is a union of intervals clipped to the window, and
``container`` ops are left out, as in ``trace.reduce``.  ``idle_gaps``
attributes each idle gap: one that lies inside an execution of the step
program to ``in:<phase>`` of the op that ends it (the device waited inside
the program, whatever the host did), one between programs to the innermost
host span (``bench.`` or ``repro.``).
"""
from __future__ import annotations

import bisect
import glob
import os
import re

from harness import trace

PASSES = ("fwd", "bwd", "update")
UPDATE = "update"           # the program's ``adversarial.UPDATE`` scope
UNSCOPED = "unscoped"
SPAN_PREFIXES = ("bench.", "repro.")
INHERIT_STEPS = 4           # XLA's copies and reverses sit 1-2 steps away

_INSTR = re.compile(r'^\s*(?:ROOT )?%?([\w.\-]+) = ')
_OP_NAME = re.compile(r'op_name="((?:[^"\\]|\\.)*)"')
_OPCODE = re.compile(r'(?:^|\s)[a-z][\w-]*\(')
_REF = re.compile(r'%([\w.\-]+)')


def phases():
    from repro.core.adversarial import PHASES
    return PHASES


def op_names(hlo_text: str) -> dict:
    """Instruction name -> its ``op_name`` ("" where it has none), for
    every instruction of a compiled module's text (``Compiled.as_text()``);
    HLO names are unique within a module."""
    out = {}
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        if m:
            op = _OP_NAME.search(line)
            out[m.group(1)] = op.group(1) if op else ""
    return out


def inherit(hlo_text: str, names: dict) -> dict:
    """``names`` with each empty op_name taken from the nearest
    instruction that uses its result, or else from the nearest that feeds
    it, within ``INHERIT_STEPS`` steps of the module's data flow.  Ops that XLA
    adds itself (async copies into fast memory, the ``reverse`` of a
    transposed conv's cotangent, layout copies) carry no metadata; they
    belong to the phase whose op they serve."""
    operands, users = {}, {}
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        if m:
            args = _operands(line[m.end():])
            operands[m.group(1)] = args
            for a in args:
                users.setdefault(a, []).append(m.group(1))
    return {n: op or _nearest(n, users, names)
            or _nearest(n, operands, names)
            for n, op in names.items()}


def _operands(rhs: str) -> list:
    """The ``%names`` in the operand list of ``<shape> <opcode>(...)``."""
    m = _OPCODE.search(rhs)
    if not m:
        return []
    level, i = 1, m.end()
    while i < len(rhs) and level:
        level += {"(": 1, ")": -1}.get(rhs[i], 0)
        i += 1
    return _REF.findall(rhs[m.end():i])


def _nearest(name, edges, names):
    frontier, seen = [name], {name}
    for _ in range(INHERIT_STEPS):
        nxt = []
        for x in frontier:
            for y in edges.get(x, ()):
                if y not in seen:
                    if names.get(y):
                        return names[y]
                    seen.add(y)
                    nxt.append(y)
        frontier = nxt
    return ""


def instruction(event_name: str) -> str:
    """``%fusion.12 = bf16[8]{0} fusion(...)`` -> ``fusion.12``."""
    m = _INSTR.match(event_name)
    return m.group(1) if m else event_name


def phase_of(op_name: str):
    """(phase, pass) of an ``op_name``, or (None, None) outside every
    phase.  The pass is ``update`` under the phase's update scope,
    ``bwd`` under a ``transpose(`` (JAX's mark of the backward pass), and
    ``fwd`` for the rest of the phase (its forward pass and the inputs it
    samples)."""
    parts = op_name.split("/")
    for i, p in enumerate(parts):
        if p in phases():
            rest = parts[i + 1:]
            if UPDATE in rest:
                return p, "update"
            if any(r.startswith("transpose(") for r in rest):
                return p, "bwd"
            return p, "fwd"
    return None, None


def host_spans(log_dir: str) -> list:
    """The program's host spans (``repro.``), which ``trace.extract``
    leaves out, listed as it lists its ``bench.`` spans: [name, start,
    duration] in ns."""
    import jax
    (path,) = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                        recursive=True)
    out = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out += [[e.name, float(e.start_ns), float(e.duration_ns)]
                        for e in line.events if e.name.startswith("repro.")]
    return out


def step_program(modules: list, window) -> str | None:
    """The program that took the most time in ``window``: the step."""
    lo, hi = window
    total = {}
    for name, s, d in modules:
        if lo <= s + d <= hi:
            total[name] = total.get(name, 0.0) + d
    return max(total, key=total.get) if total else None


def _runs(modules, program):
    return sorted([s, s + d] for name, s, d in modules if name == program)


def _inside(runs, starts, s, e):
    """The run of ``runs`` (sorted, disjoint) holding [s, e], or None."""
    i = bisect.bisect_right(starts, s) - 1
    if i >= 0 and e <= runs[i][1]:
        return runs[i]
    return None


def reduce(extracted: dict, window, names: dict, pats: dict) -> dict:
    """Per device: the step program's op time in ``window`` (s) by phase
    and pass, the ``unscoped`` rest, the part of it whose instruction is
    not in ``names`` (``unmapped_s``), its busy time (the union of all of
    its ops) and its executions that end in the window."""
    lo, hi = window
    out = {}
    for dev, events in sorted(extracted["devices"].items()):
        mods = extracted["modules"].get(dev, [])
        program = step_program(mods, window)
        runs = _runs(mods, program)
        starts = [s for s, _ in runs]
        groups = {(p, q): [] for p in phases() for q in PASSES}
        groups[UNSCOPED] = []
        every, unmapped = [], []
        for name, s, d in events:
            if trace.classify(name, pats) == "container" or \
                    _inside(runs, starts, s, s + d) is None:
                continue
            instr = instruction(name)
            key = phase_of(names.get(instr, ""))
            groups[key if key[0] else UNSCOPED].append([s, s + d])
            every.append([s, s + d])
            if instr not in names:
                unmapped.append([s, s + d])

        def secs(iv):
            return trace._length(trace._union(trace._clip(iv, lo, hi))) / 1e9
        out[dev] = {
            "program": program,
            "steps": sum(1 for s, e in runs if lo <= e <= hi),
            "busy_s": secs(every),
            "phases": {p: {q: secs(groups[(p, q)]) for q in PASSES}
                       for p in phases()},
            UNSCOPED + "_s": secs(groups[UNSCOPED]),
            "unmapped_s": secs(unmapped),
        }
    return out


def phase_ms_per_step(phase_summary: dict, phase: str):
    """Device ms per step under ``phase``, its update included, averaged
    over chips; None without a step in the window."""
    vals = [1e3 * sum(d["phases"][phase].values()) / d["steps"]
            for d in phase_summary.values() if d["steps"]]
    return sum(vals) / len(vals) if vals else None


def idle_gaps(extracted: dict, window, names: dict, pats: dict) -> dict:
    """Seconds of idle time on the first device in ``window``, by where it
    lies: ``in:<phase>`` (or ``in:unscoped``) inside an execution of the
    step program, named by the op that ends the gap; between programs,
    the innermost host span whose name starts with ``SPAN_PREFIXES``."""
    lo, hi = window
    dev = min(extracted["devices"])
    mods = extracted["modules"].get(dev, [])
    runs = _runs(mods, step_program(mods, window))
    starts = [s for s, _ in runs]
    ops = [[s, s + d, name] for name, s, d in extracted["devices"][dev]
           if trace.classify(name, pats) != "container"]
    first_at = {}
    for s, e, name in sorted(ops, key=lambda x: (x[0], -x[1])):
        first_at.setdefault(s, name)
    work = trace._union(trace._clip([[s, e] for s, e, _ in ops], lo, hi))
    host = sorted(([s, s + d, n] for n, s, d in extracted["host"]
                   if n.startswith(SPAN_PREFIXES)), key=lambda x: x[0])
    gaps = {}
    edges = [lo] + [x for iv in work for x in iv] + [hi]
    for gs, ge in zip(edges[0::2], edges[1::2]):
        if ge <= gs:
            continue
        if _inside(runs, starts, gs, ge) is not None and ge in first_at:
            p, _ = phase_of(names.get(instruction(first_at[ge]), ""))
            where = "in:" + (p or UNSCOPED)
        else:
            where = trace._span_at(host, (gs + ge) / 2)
        gaps[where] = gaps.get(where, 0.0) + (ge - gs) / 1e9
    return gaps

"""The LM block's named scopes in the device trace of the training step.

The step runs latent attention under ``mla``, the MoE layer under ``moe``
with ``route``, ``dispatch``, ``experts``, ``shared`` and ``combine``
inside it, the dense layer's FFN under ``dense_ffn`` and the head and
its loss under ``lm_head`` (``repro.substrate.attention.apply_mla``,
``repro.substrate.moe.apply_grouped``, ``repro.models.lm``).  XLA keeps
the scopes in each instruction's ``op_name``, where JAX wraps them as
``jvp(...)`` and marks the backward pass ``transpose(...)``; the map from
instruction to op_name comes from the compiled step's text
(``scopes.op_names`` / ``scopes.inherit``), as for the GAN's phases.

XLA's TPU lowering of ``jax.lax.ragged_dot`` replaces each grouped
matmul with custom calls of its own naming (``op_name="ragged-dot-none"``,
``"ragged-dot-metadata"``), which keep no name stack; the program calls
``ragged_dot`` under ``moe/experts`` alone, so ``op_scopes`` puts those
calls there, in the pass of the op they serve.

``reduce`` splits the step program's op time inside a window by scope
and pass (``fwd``, ``bwd``: rematerialised forward work that the
backward pass repeats counts as ``bwd``), with an ``unscoped`` rest
(norms, residual adds, the embedding, the optimizer); ``container`` ops
are left out, as in ``trace.reduce``.  A ``lax.cond`` op spans the ops of
the branch it runs: only the part of it that no other op covers counts,
under its own scope, so its time is neither counted twice nor lost
whether or not the trace shows its branch's ops (``cond_s`` and
``cond_uncovered_s`` say which).  Scoped plus unscoped is the step
program's busy time, up to ops that overlap across scopes.
"""
from __future__ import annotations

import bisect
import re

from harness import scopes, trace

SCOPES = ("mla", "moe/route", "moe/dispatch", "moe/experts", "moe/shared",
          "moe/combine", "dense_ffn", "lm_head")
MOE_PARTS = ("route", "dispatch", "experts", "shared", "combine")
PASSES = ("fwd", "bwd")
UNSCOPED = "unscoped"

# a ``lax.cond`` (``%cond.N = ... conditional(...)``) spans the ops of the
# branch it runs
COND = re.compile(r"^%[\w.\-]+ = .*? conditional\(")
# the op_names XLA gives the custom calls of a grouped matmul on TPU
XLA_RAGGED = re.compile(r"^ragged-dot")
_WRAPPED = re.compile(r"^(?:[\w.]+\()*([^()]*)\)*$")


def _bare(part: str) -> str:
    """``transpose(jvp(lm_head))`` -> ``lm_head``."""
    m = _WRAPPED.match(part)
    return m.group(1) if m else part


def scope_of(op_name: str):
    """(scope, pass) of an ``op_name``, or (None, None) outside every
    scope.  Under ``moe`` the innermost of its parts names the scope; an
    op of ``moe`` itself (adding the chunks' outputs) is ``moe/combine``."""
    if not op_name:
        return None, None
    parts = op_name.split("/")
    names = [_bare(p) for p in parts]
    step = "bwd" if any(p.startswith("transpose(") for p in parts) else "fwd"
    if "moe" in names:
        inner = [n for n in names[names.index("moe") + 1:] if n in MOE_PARTS]
        return "moe/" + (inner[-1] if inner else "combine"), step
    for s in ("mla", "dense_ffn", "lm_head"):
        if s in names:
            return s, step
    return None, None


def op_scopes(hlo_text: str) -> dict:
    """Instruction name -> op_name, with XLA's own ops given the op_name
    of the op they serve, and the grouped matmuls' custom calls put under
    ``moe/experts`` in the pass of the op they serve."""
    names = scopes.op_names(hlo_text)
    ragged = {n for n, op in names.items() if XLA_RAGGED.match(op)}
    names = scopes.inherit(hlo_text, {n: "" if n in ragged else op
                                      for n, op in names.items()})
    for n in ragged:
        names[n] = _as_experts(names[n])
    return names


def _as_experts(op_name: str) -> str:
    """``op_name`` of a neighbour -> that of a grouped matmul beside it:
    its path down to ``moe``'s innermost part, which becomes ``experts``."""
    parts = op_name.split("/") if op_name else []
    names = [_bare(p) for p in parts]
    if "moe" in names:
        inner = [i for i in range(names.index("moe") + 1, len(names))
                 if names[i] in MOE_PARTS]
        cut = inner[-1] if inner else names.index("moe") + 1
        return "/".join(parts[:cut] + ["experts", "ragged_dot"])
    return "/".join(parts + ["moe", "experts", "ragged_dot"])


def reduce(extracted: dict, window, names: dict, pats: dict) -> dict:
    """Per device: the step program's op time in ``window`` (s) by scope
    and pass, the ``unscoped`` rest, the part whose instruction is not in
    ``names`` (``unmapped_s``), its busy time and its executions that end
    in the window."""
    lo, hi = window
    out = {}
    for dev, events in sorted(extracted["devices"].items()):
        mods = extracted["modules"].get(dev, [])
        runs = scopes._runs(mods, scopes.step_program(mods, window))
        starts = [s for s, _ in runs]
        groups = {(s, p): [] for s in SCOPES for p in PASSES}
        groups[UNSCOPED] = []
        every, unmapped, conds = [], [], []
        for name, s, d in events:
            if trace.classify(name, pats) == "container" or \
                    scopes._inside(runs, starts, s, s + d) is None:
                continue
            instr = scopes.instruction(name)
            key = scope_of(names.get(instr, ""))
            key = key if key[0] else UNSCOPED
            if instr not in names:
                unmapped.append([s, s + d])
            if COND.search(name):
                conds.append((key, [s, s + d]))
                continue
            groups[key].append([s, s + d])
            every.append([s, s + d])
        covered = trace._union(every)
        ends = [b for _, b in covered]
        uncovered = []
        for key, iv in conds:
            rest = _uncovered(iv, covered, ends)
            groups[key] += rest
            uncovered += rest

        def secs(iv):
            return trace._length(trace._union(trace._clip(iv, lo, hi))) / 1e9
        out[dev] = {
            "steps": sum(1 for s, e in runs if lo <= e <= hi),
            "busy_s": secs(every + uncovered),
            "scopes": {s: {p: secs(groups[(s, p)]) for p in PASSES}
                       for s in SCOPES},
            UNSCOPED + "_s": secs(groups[UNSCOPED]),
            "unmapped_s": secs(unmapped),
            "cond_s": secs([iv for _, iv in conds]),
            "cond_uncovered_s": secs(uncovered),
        }
    return out


def _uncovered(iv, covered, ends):
    """The parts of interval ``iv`` that no interval of ``covered``
    (merged, sorted; ``ends`` their ends) overlaps."""
    s, e = iv
    out, cur = [], s
    i = bisect.bisect_right(ends, s)
    for a, b in covered[i:]:
        if a >= e:
            break
        if a > cur:
            out.append([cur, a])
        cur = max(cur, b)
    if cur < e:
        out.append([cur, e])
    return out


def ms_per_step(summary: dict, prefix: str):
    """Device ms per step under the scopes that start with ``prefix``,
    both passes, averaged over chips; None without a step."""
    vals = [1e3 * sum(sum(v.values()) for s, v in d["scopes"].items()
                      if s.startswith(prefix)) / d["steps"]
            for d in summary.values() if d["steps"]]
    return sum(vals) / len(vals) if vals else None

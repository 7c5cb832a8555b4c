"""Device time by program layer: the compiled step's named scopes.

The program names its layers with ``jax.named_scope`` (``layer.*`` in the
transformer block, ``model.*`` around the embedding and the head,
``engine.*`` around the simplex engine's launch). A scope lands in each
compiled instruction's ``op_name`` metadata, and the trace's device ops
are those same instructions, named alike, so each op's self time is
charged to the innermost scope of its instruction. The trace carries no
metadata itself (the profiler is started without the HLO proto), so the
map comes from the step's compiled HLO text
(``jit(f).lower(...).compile().as_text()``).

An instruction with no ``op_name``, such as a copy that XLA inserted,
takes the scope of the operand it reads. What is still unscoped is
charged to ``(unscoped)``, never dropped.
"""

from __future__ import annotations

import re
import time

from .trace import self_times

__all__ = ["PREFIXES", "UNSCOPED", "op_scopes", "scope_of", "scope_self_ns",
           "shares_line", "step_scopes"]

PREFIXES = ("layer.", "model.", "engine.")
UNSCOPED = "(unscoped)"
PALLAS_SUFFIX = " (pallas)"

_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%([^\s=]+)\s*=\s*(.*)$")
_OPCODE = re.compile(r"\s([a-z][\w\-]*)\(")
_OPERAND = re.compile(r"%([\w.\-]+)")
_OP_NAME = re.compile(r'op_name="([^"]*)"')


def scope_of(op_name: str) -> str | None:
    """The innermost component of an ``op_name`` path that names a scope."""
    found = None
    for part in op_name.split("/"):
        if part.startswith(PREFIXES):
            found = part
    return found


def _operands(rest: str) -> list:
    """Names of the instructions an instruction reads (its first parentheses)."""
    m = _OPCODE.search(rest)
    if not m:
        return []
    depth, start = 0, m.end() - 1
    for i in range(start, len(rest)):
        depth += {"(": 1, ")": -1}.get(rest[i], 0)
        if depth == 0:
            return _OPERAND.findall(rest[start:i])
    return _OPERAND.findall(rest[start:])


def op_scopes(hlo_text: str) -> dict:
    """``{instruction name: scope}`` for every instruction of ``hlo_text``.

    The scope is the innermost ``op_name`` path component that starts with
    ``layer.``, ``model.`` or ``engine.``; a fusion takes its own
    instruction's metadata. An instruction with no ``op_name`` takes the
    scope of the first operand that has one (operands come before their
    users in the text); what finds none maps to ``(unscoped)``.
    """
    out: dict = {}
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        if not m:
            continue
        name, rest = m.groups()
        op = _OP_NAME.search(rest)
        if op:
            out[name] = scope_of(op.group(1)) or UNSCOPED
        else:
            out[name] = next((out[o] for o in _operands(rest)
                              if out.get(o, UNSCOPED) != UNSCOPED), UNSCOPED)
    return out


def scope_self_ns(summary, scopes: dict) -> dict:
    """Self time (ns) of chip 0's ops, summed by scope.

    An op the map does not name is charged to ``(unscoped)``.
    """
    out: dict = {}
    for name, ns in self_times(summary.ops(chip=0)).items():
        if name.endswith(PALLAS_SUFFIX):
            name = name[:-len(PALLAS_SUFFIX)]
        scope = scopes.get(name, UNSCOPED)
        out[scope] = out.get(scope, 0.0) + ns
    return out


def shares_line(by_scope: dict) -> str:
    """One line: every scope's share of busy time, largest first."""
    busy = sum(by_scope.values())
    if busy <= 0:
        return "device time by scope: no device op"
    parts = [f"{s} {100.0 * ns / busy:.2f}%"
             for s, ns in sorted(by_scope.items(), key=lambda kv: -kv[1])]
    return "device time by scope (share of busy): " + ", ".join(parts)


def step_scopes(summary, facts: dict) -> dict | None:
    """The run's ``{scope: self ns}``, computed once and kept in ``facts``.

    The map of instructions to scopes is ``facts["op_scopes"]`` when the
    driver gave one; otherwise the cell's step is compiled again from
    ``facts`` (``bench.core.steps``), which the persistent compilation
    cache answers with the executable the window ran. The first call
    prints the shares of busy time. None when the step cannot be rebuilt
    or the trace holds no device op.
    """
    if "scope_self_ns" not in facts:
        by_scope = None
        scopes = facts.get("op_scopes")
        if scopes is None:
            from .steps import compiled_hlo

            t = time.perf_counter()
            text = compiled_hlo(summary, facts)
            scopes = op_scopes(text) if text else None
            facts["op_scopes"] = scopes
            print(f"bench: the step's HLO text took {time.perf_counter() - t:.3f} s",
                  flush=True)
        if scopes is not None and summary.ops(chip=0):
            by_scope = scope_self_ns(summary, scopes)
            print(f"bench: {shares_line(by_scope)}", flush=True)
        facts["scope_self_ns"] = by_scope
    return facts["scope_self_ns"]

"""Which ``lgbm/`` scope each operation of a compiled program runs under.

The fused step wraps its parts in ``jax.named_scope("lgbm/...")``
(``ops/step_cache.py``, ``ops/wave_grower.py``, ``parallel/learners.py``)
and the compiler keeps that name in every instruction's
``metadata={op_name="jit(step)/.../lgbm/wave/split_find/..."}``. A device
event of a profiler trace carries the instruction's NAME (``fusion.347``,
``fused_partition_histogram_pallas.8``) and not its metadata. This module
gives the join: ``op_scopes()`` maps each instruction name of the programs
the process watched to its innermost ``lgbm/`` scope, so a reader of a
trace can put device self time down to the program's own parts.

- **What is watched.** ``watch(label, fn, args)`` keeps a jitted function
  and the ABSTRACT signature of one call (``ShapeDtypeStruct``s with the
  shardings of committed arguments; never a buffer). The step cache
  watches each step at its first dispatch, the booster the stop check's
  stacked download when it compiles it in ``init``. Watching costs a
  ``tree_map`` over the arguments; nothing is lowered.
- **Built on the first ask, never before.** ``op_scopes()`` lowers each
  watched function on its signature, compiles it and parses
  ``compiled.as_text()``. The signature is the one jax keyed its own
  caches on, so the lowering and the compile are those of the call that
  ran (a cache hit, milliseconds) while the function lives; after the
  caches were dropped it is a compile from the same HLO. The build runs
  under the span ``obs/op_scopes`` (its timer says what asking cost) and
  is kept; a later watch adds to it on the next ask.
- **The scope.** The path components after the LAST ``lgbm`` component
  of ``op_name``, up to the first component that is not a scope's
  (a transformation's ``jit(..)`` / ``vmap(..)``, a control-flow body,
  the primitive's own name at the end): ``.../lgbm/wave/hist_psum/
  all-reduce`` is ``lgbm/wave/hist_psum``, which is NOT under
  ``lgbm/wave/hist`` (``under`` compares whole components), and
  ``.../lgbm/gradients/rank_pairs/mul`` is ``lgbm/gradients/rank_pairs``,
  which IS under ``lgbm/gradients``. An instruction the compiler made
  with no metadata (a copy it inserted, an async start or done, a fusion
  it named itself) takes the scope of its fused computation, else of
  its first operand that has one, else of the control flow that runs
  it. An instruction whose ``op_name`` names program code outside every
  ``lgbm/`` scope maps to None: that is code no scope wraps yet.
- **Names two programs share.** An instruction name is unique inside
  one program, not across them; a trace sums the time of every program's
  ``copy.3`` under one name. Where two watched programs give a name
  different scopes, the step's wins (it runs every iteration; the other
  watched programs run once an interval).
"""
from __future__ import annotations

import re
import threading
import weakref
from collections import Counter
from typing import Dict, List, Optional

from . import trace

__all__ = ["watch", "op_scopes", "scope_of", "under", "parse_hlo",
           "BUILD_SPAN", "STEP_LABEL"]

BUILD_SPAN = "obs/op_scopes"
STEP_LABEL = "train_step"
SCOPE_ROOT = "lgbm"
MAX_PROGRAMS = 64

# path components jax writes for control flow and calls, never a scope's
_STRUCTURAL = frozenset({"while", "body", "cond", "closed_call",
                         "core_call", "remat", "checkpoint", "shard_map",
                         "scan", "custom_jvp_call", "custom_vjp_call"})
_COMPONENT = re.compile(r"[a-z0-9_]+")
_PRIMITIVE = re.compile(r"[\w\-]+")

_lock = threading.Lock()
_programs: List[tuple] = []      # guarded-by: _lock
_parsed: Dict[tuple, tuple] = {}     # guarded-by: _lock


def scope_of(op_name: str) -> Optional[str]:
    """The innermost ``lgbm/`` scope in an instruction's ``op_name``, or
    None where no ``lgbm`` component names one."""
    parts = op_name.split("/")
    last = max((i for i, p in enumerate(parts) if p == SCOPE_ROOT),
               default=None)
    if last is None:
        return None
    out = [SCOPE_ROOT]
    for p in parts[last + 1:-1]:         # the last component: the primitive
        if (not _COMPONENT.fullmatch(p) or p in _STRUCTURAL
                or p.startswith("branch_")):
            break
        out.append(p)
    return "/".join(out) if len(out) > 1 else None


def _program_code(op_name: Optional[str]) -> bool:
    """An ``op_name`` that names a primitive of the program's code (its
    last component), not an argument's name or a jit's alone: the
    compiler's own constants and layout copies have none."""
    if not op_name or "/" not in op_name:
        return False
    return bool(_PRIMITIVE.fullmatch(op_name.rsplit("/", 1)[1]))


def under(scope: Optional[str], parent: str) -> bool:
    """``scope`` is ``parent`` or lies inside it, component by component."""
    return scope is not None and (scope == parent
                                  or scope.startswith(parent + "/"))


# -- the compiled program's text ------------------------------------------------

_HEADER = re.compile(r"^(ENTRY\s+)?%?([^\s(]+)\s*\(.*\{\s*$")
_INSTR = re.compile(r"^\s+(?:ROOT\s+)?%?([^\s=]+)\s+=\s+(.*)$")
_OPCODE = re.compile(r"(?:^|\s)([a-z][a-z0-9\-]*)\(")
_OP_NAME = re.compile(r'op_name="((?:[^"\\]|\\.)*)"')
_REF = re.compile(r"%([\w.\-]+)")
_CALLS = re.compile(r"\b(calls|to_apply|body|condition|true_computation|"
                    r"false_computation)=%?([\w.\-]+)")
_BRANCHES = re.compile(r"branch_computations=\{([^}]*)\}")


def _operands(rest: str, at: int) -> List[str]:
    """Names of the operands in the parenthesised list opening at
    ``rest[at]``."""
    depth = 0
    for j in range(at, len(rest)):
        c = rest[j]
        if c == "(":
            depth += 1
        elif c == ")":
            depth -= 1
            if depth == 0:
                return _REF.findall(rest[at:j])
    return _REF.findall(rest[at:])


def parse_hlo(text: str) -> Dict[str, Optional[str]]:
    """``{instruction name: scope or None}`` for every instruction of the
    computations that run as operations of an HLO module's text (the entry
    computation and, through it, while bodies and conditions, conditional
    branches and called computations; not fused or reducer computations)."""
    comps: Dict[str, list] = {}
    entry = None
    cur = None
    for line in text.splitlines():
        if not line[:1].isspace():
            m = _HEADER.match(line)
            if m:
                cur = comps.setdefault(m.group(2), [])
                entry = m.group(2) if m.group(1) else entry
            elif line.startswith("}"):
                cur = None
            continue
        if cur is None:
            continue
        m = _INSTR.match(line)
        if not m:
            continue
        rest = m.group(2)
        op = _OPCODE.search(rest)
        name_m = _OP_NAME.search(rest)
        called = {}
        for key, comp in _CALLS.findall(rest):
            called.setdefault(key, []).append(comp)
        for grp in _BRANCHES.findall(rest):
            called.setdefault("branch", []).extend(
                b.strip().lstrip("%") for b in grp.split(",") if b.strip())
        cur.append({
            "name": m.group(1),
            "opcode": op.group(1) if op else "",
            "op_name": name_m.group(1) if name_m else None,
            "operands": _operands(rest, op.end() - 1) if op else [],
            "called": called,
        })
    if entry is None:
        return {}

    def fused_scope(comp: str) -> Optional[str]:
        insts = comps.get(comp, [])
        scopes = [scope_of(i["op_name"]) for i in insts if i["op_name"]]
        scopes = [s for s in scopes if s]
        if not scopes:
            return None
        return Counter(scopes).most_common(1)[0][0]

    table: Dict[str, Optional[str]] = {}
    todo = [(entry, None)]
    seen = set()
    while todo:
        comp, outer = todo.pop()
        if comp in seen or comp not in comps:
            continue
        seen.add(comp)
        insts = comps[comp]
        made = [not _program_code(i["op_name"]) for i in insts]
        made_names = {i["name"] for i, m in zip(insts, made) if m}
        for inst, by_compiler in zip(insts, made):
            scope = None
            if not by_compiler:
                scope = scope_of(inst["op_name"])
            else:
                if inst["opcode"] == "fusion":
                    scope = fused_scope((inst["called"].get("calls")
                                         or [""])[0])
                if scope is None:
                    scope = next((table[o] for o in inst["operands"]
                                  if table.get(o)), None)
            table[inst["name"]] = scope
        # what the compiler made for a user alone (a copy of an argument
        # into another layout) is that user's
        for inst in reversed(insts):
            for o in inst["operands"]:
                if table.get(o) is None and o in made_names:
                    table[o] = table[inst["name"]]
        for inst, by_compiler in zip(insts, made):
            if by_compiler and table[inst["name"]] is None:
                table[inst["name"]] = outer
            runs = []
            if inst["opcode"] == "while":
                runs = (inst["called"].get("body", [])
                        + inst["called"].get("condition", []))
            elif inst["opcode"] == "conditional":
                runs = (inst["called"].get("branch", [])
                        + inst["called"].get("true_computation", [])
                        + inst["called"].get("false_computation", []))
            elif inst["opcode"] == "call":
                runs = inst["called"].get("to_apply", [])
            todo.extend((c, table[inst["name"]]) for c in runs)
    return table


# -- the programs of this process ----------------------------------------------

def _abstract(x):
    """An argument as the signature jax keyed its caches on: shape, dtype,
    weak type, and the sharding only where the array was committed to it
    (an uncommitted array lowers with none)."""
    import jax
    if not isinstance(x, jax.Array):
        return x
    committed = getattr(x, "_committed", True)
    return jax.ShapeDtypeStruct(x.shape, x.dtype, weak_type=x.weak_type,
                                sharding=x.sharding if committed else None)


def watch(label: str, fn, args: tuple) -> None:
    """Keep ``fn`` (weakly) and the abstract signature of ``fn(*args)``,
    for ``op_scopes`` to lower on its first ask. A signature already
    watched for ``fn`` is kept once."""
    import jax
    sig = jax.tree_util.tree_map(_abstract, args)
    leaves, tree = jax.tree_util.tree_flatten(sig)
    key = (id(fn), tree, tuple(leaves))
    with _lock:
        if any(p[0] == key for p in _programs):
            return
        _programs.append((key, label, weakref.ref(fn), sig))
        del _programs[:-MAX_PROGRAMS]


def op_scopes() -> Dict[str, Optional[str]]:
    """``{instruction name: innermost lgbm/ scope or None}`` over every
    watched program that is still alive, each built on the first ask
    after it was watched (see the module's docstring) and kept."""
    with _lock:
        alive = [(p, p[2]()) for p in _programs]
        _programs[:] = [p for p, fn in alive if fn is not None]
        for key in set(_parsed) - {p[0] for p in _programs}:
            del _parsed[key]
        todo = [(p, fn) for p, fn in alive
                if fn is not None and p[0] not in _parsed]
        if todo:
            with trace.span(BUILD_SPAN, args={"programs": len(todo)}):
                for (key, label, _ref, sig), fn in todo:
                    text = fn.lower(*sig).compile().as_text()
                    _parsed[key] = (label, parse_hlo(text))
        table: Dict[str, Optional[str]] = {}
        # the step's names last: where a name is shared, its scope wins
        for label, part in sorted(_parsed.values(),
                                  key=lambda p: p[0] == STEP_LABEL):
            table.update(part)
        return table


def clear() -> None:
    """Forget every watched program and the table (tests)."""
    with _lock:
        _programs.clear()
        _parsed.clear()

"""jit-capture checker: compiled code must not close over arrays.

The two nastiest historical bugs in this repo were closure-capture
bugs in jitted/cached code paths:

- **PR 5 (closure recapture)**: the fused training step lived in a
  per-booster closure; after the process-wide registry landed, any
  regression that silently re-captured per-booster state (labels,
  score buffers) would either bake one booster's arrays into a SHARED
  compiled program or put every booster back on its own compile. The
  only guard was a runtime conftest hit-rate assertion.
- **PR 7 (captured device arrays)**: a predict-registry wrapper closed
  over the first model's device stacks — a registry hit from a
  retrained same-geometry model would have served the FIRST model's
  arrays. Caught by a parity suite, after the fact.

This checker moves both to analysis time. Any function that is

- passed to ``jax.jit`` (call, ``@jax.jit``, ``@partial(jax.jit,..)``),
- returned by a builder registered in ``step_cache.get_step`` /
  ``predict_cache.get`` / ``StackedModel._dispatch``,

must close only over an allowlist of **static kinds**:

- module globals and builtins (not per-instance state);
- values provably scalar/hashable-static: constants, ``int()/float()/
  bool()/str()/len()/tuple()/...`` results, boolean expressions,
  arithmetic over statics, ``Config`` scalar fields (``cfg.lambda_l1``
  — the "config scalars" contract of ``gradient_builder``);
- parameters of enclosing functions whose annotation is a static type
  (``int``, ``float``, ``bool``, ``str``, ``tuple``, ``Optional`` of
  those).

Anything else — ``self``/attribute reads, results of arbitrary calls
(``jnp.asarray(...)``, ``self._device_arrays(...)``), unannotated or
``Callable`` parameters, nested closures — is flagged: those are
exactly the kinds that can bind arrays or per-booster state.

Deliberate captures (a per-instance jit whose closed-over tables ARE
the kernel constants) are waived INLINE, next to the code, with a
reason::

    # jit-capture: ok(nan_bin, cats) — per-binner jit, tables are
    # per-dataset constants
    return jax.jit(chunk)

``ok(*)`` waives every capture of a plain ``jax.jit`` site; registry
registrations accept only NAMED waivers (a shared program must
enumerate what it closes over). The checker's baseline must stay
empty — exemptions live next to the code they excuse.
"""
from __future__ import annotations

import ast
import re
from typing import Dict, List, Optional, Sequence, Set, Tuple

from .core import Finding, SourceFile, call_name, dotted

CHECKER = "jit_capture"

# call targets that register a builder whose RESULT is cached
# process-wide (named waivers only — these programs outlive a booster)
REGISTRY_CALLS = {"step_cache.get_step", "predict_cache.get"}
REGISTRY_CALL_SUFFIXES = ("._dispatch",)
# builder-returned calls that are themselves audited jit factories:
# a builder returning one of these delegates its capture contract to
# the factory's own jit site (checked at that site)
AUDITED_BUILDER_FACTORIES = {"step_cache.build_train_step",
                             "build_train_step"}

STATIC_CALL_NAMES = {
    "int", "float", "bool", "str", "len", "min", "max", "round",
    "abs", "tuple", "sorted", "range", "frozenset", "repr", "hash",
}
STATIC_METHOD_NAMES = {"bit_length"}
STATIC_ANNOTATION_NAMES = {"int", "float", "bool", "str", "tuple",
                           "Tuple", "frozenset", "FrozenSet"}

_WAIVER_RE = re.compile(
    r"jit-capture:\s*ok\(([^)]*)\)\s*[-—:]*\s*(\S.*)?")


class _Waivers:
    def __init__(self, names: Set[str], wildcard: bool):
        self.names = names
        self.wildcard = wildcard

    def covers(self, name: str, allow_wildcard: bool) -> bool:
        return name in self.names or (self.wildcard and allow_wildcard)


def _parse_waivers(*comments: str) -> Optional[_Waivers]:
    names: Set[str] = set()
    wildcard = False
    seen = False
    for c in comments:
        for m in _WAIVER_RE.finditer(c or ""):
            if not (m.group(2) or "").strip():
                continue        # a waiver without a reason is no waiver
            seen = True
            for tok in m.group(1).split(","):
                tok = tok.strip()
                if tok == "*":
                    wildcard = True
                elif tok:
                    names.add(tok)
    return _Waivers(names, wildcard) if seen else None


# ---------------------------------------------------------------------------
# Static-kind inference
# ---------------------------------------------------------------------------

class _Kinds:
    """Conservative static-expression classifier over one file."""

    def __init__(self, sf: SourceFile, config_fields: Set[str]):
        self.sf = sf
        self.config_fields = config_fields

    # -- scope bindings -----------------------------------------------------

    def _bindings(self, fn: ast.AST, name: str) -> List[ast.AST]:
        """Binding sites of ``name`` local to function ``fn`` (not
        descending into nested functions): parameter nodes, assignment
        value expressions, or the binding statement itself."""
        out: List[ast.AST] = []
        args = getattr(fn, "args", None)
        if args is not None:
            for a in (args.posonlyargs + args.args + args.kwonlyargs
                      + ([args.vararg] if args.vararg else [])
                      + ([args.kwarg] if args.kwarg else [])):
                if a.arg == name:
                    out.append(a)

        def visit(node: ast.AST):
            for child in ast.iter_child_nodes(node):
                if isinstance(child, (ast.FunctionDef,
                                      ast.AsyncFunctionDef,
                                      ast.Lambda, ast.ClassDef)):
                    if isinstance(child, (ast.FunctionDef,
                                          ast.AsyncFunctionDef,
                                          ast.ClassDef)) \
                            and child.name == name:
                        out.append(child)
                    continue            # new scope: don't descend
                if isinstance(child, ast.Assign):
                    for t in child.targets:
                        self._match_target(t, name, child.value, out)
                elif isinstance(child, ast.AnnAssign) and child.value:
                    self._match_target(child.target, name, child.value,
                                       out)
                elif isinstance(child, ast.AugAssign):
                    self._match_target(child.target, name, child, out)
                elif isinstance(child, (ast.For, ast.AsyncFor)):
                    self._match_target(child.target, name, child, out)
                elif isinstance(child, (ast.With, ast.AsyncWith)):
                    for item in child.items:
                        if item.optional_vars is not None:
                            self._match_target(item.optional_vars,
                                               name, child, out)
                elif isinstance(child, ast.NamedExpr):
                    self._match_target(child.target, name, child.value,
                                       out)
                elif isinstance(child, (ast.Import, ast.ImportFrom)):
                    for alias in child.names:
                        bound = (alias.asname
                                 or alias.name.split(".")[0])
                        if bound == name:
                            out.append(child)
                visit(child)

        body = getattr(fn, "body", None)
        if isinstance(body, list):
            for stmt in body:
                visit_root = ast.Module(body=[stmt], type_ignores=[])
                visit(visit_root)
        return out

    @staticmethod
    def _match_target(target: ast.AST, name: str, value: ast.AST,
                      out: List[ast.AST]) -> None:
        if isinstance(target, ast.Name) and target.id == name:
            out.append(value)
        elif isinstance(target, (ast.Tuple, ast.List)):
            for i, elt in enumerate(target.elts):
                if isinstance(elt, ast.Name) and elt.id == name:
                    # tuple unpack: classify the matching element when
                    # the value is a literal tuple, else the whole RHS
                    if isinstance(value, (ast.Tuple, ast.List)) \
                            and len(value.elts) == len(target.elts):
                        out.append(value.elts[i])
                    else:
                        out.append(value)
                elif isinstance(elt, (ast.Tuple, ast.List)):
                    _Kinds._match_target(elt, name, value, out)

    # -- classification -----------------------------------------------------

    def classify_free(self, name: str, scopes: Sequence[ast.AST],
                      _depth: int = 0) -> Tuple[bool, str]:
        """(is_static, why-not) for a name captured from the given
        innermost-first chain of enclosing function scopes."""
        for fn in scopes:
            sites = self._bindings(fn, name)
            if not sites:
                continue
            idx = list(scopes).index(fn)
            for site in sites:
                if isinstance(site, ast.arg):
                    ok, why = self._param_static(site)
                elif isinstance(site, (ast.FunctionDef,
                                       ast.AsyncFunctionDef,
                                       ast.ClassDef)):
                    ok, why = False, "a nested closure (may capture " \
                                     "arrays transitively)"
                elif isinstance(site, (ast.Import, ast.ImportFrom)):
                    ok, why = True, ""
                elif isinstance(site, (ast.For, ast.AsyncFor, ast.With,
                                       ast.AsyncWith, ast.AugAssign)):
                    ok, why = False, "bound by a loop/with/augmented " \
                                     "assignment"
                else:
                    ok, why = self.expr_static(site, scopes[idx:],
                                               _depth + 1)
                if not ok:
                    return False, why
            return True, ""
        return False, "no static binding found in enclosing scopes"

    def _param_static(self, a: ast.arg) -> Tuple[bool, str]:
        if a.annotation is not None and \
                self._ann_static(a.annotation):
            return True, ""
        ann = ast.unparse(a.annotation) if a.annotation else "unannotated"
        return False, (f"an enclosing-scope parameter ({ann}) — only "
                       "int/float/bool/str/tuple-annotated parameters "
                       "are provably static")

    def _ann_static(self, ann: ast.AST) -> bool:
        if isinstance(ann, ast.Name):
            return ann.id in STATIC_ANNOTATION_NAMES
        if isinstance(ann, ast.Attribute):
            return ann.attr in STATIC_ANNOTATION_NAMES
        if isinstance(ann, ast.Subscript):
            base = dotted(ann.value)
            tail = base.rsplit(".", 1)[-1]
            if tail == "Optional":
                return self._ann_static(ann.slice)
            return tail in STATIC_ANNOTATION_NAMES
        if isinstance(ann, ast.Constant) and isinstance(ann.value, str):
            try:
                return self._ann_static(
                    ast.parse(ann.value, mode="eval").body)
            except SyntaxError:
                return False
        return False

    def expr_static(self, e: ast.AST, scopes: Sequence[ast.AST],
                    _depth: int = 0) -> Tuple[bool, str]:
        """Is the value of expression ``e`` a static kind?"""
        if _depth > 12:
            return False, "expression too deep to classify"
        if isinstance(e, ast.Constant):
            return True, ""
        if isinstance(e, ast.Name):
            # local/enclosing binding, else a module global (process-
            # wide, not per-booster — allowed)
            for fn in scopes:
                if self._bindings(fn, e.id):
                    return self.classify_free(e.id, scopes, _depth)
            return True, ""
        if isinstance(e, ast.Attribute):
            if e.attr in self.config_fields:
                return True, ""     # Config scalar — the contract kind
            return False, (f"an attribute read ({ast.unparse(e)}) — "
                           "can bind arrays or per-instance state")
        if isinstance(e, ast.Call):
            fname = call_name(e)
            if fname.rsplit(".", 1)[-1] in STATIC_CALL_NAMES and \
                    "." not in fname:
                return True, ""
            if isinstance(e.func, ast.Attribute) and \
                    e.func.attr in STATIC_METHOD_NAMES:
                return True, ""
            return False, (f"the result of a call ({fname or '?'}(...))"
                           " — not provably static")
        if isinstance(e, ast.UnaryOp):
            if isinstance(e.op, ast.Not):
                return True, ""     # bool result
            return self.expr_static(e.operand, scopes, _depth + 1)
        if isinstance(e, ast.Compare):
            if all(isinstance(op, (ast.Is, ast.IsNot, ast.In,
                                   ast.NotIn)) for op in e.ops):
                return True, ""     # identity/membership: bool result
            for sub in [e.left] + list(e.comparators):
                ok, why = self.expr_static(sub, scopes, _depth + 1)
                if not ok:
                    return ok, why
            return True, ""
        if isinstance(e, ast.BoolOp):
            for sub in e.values:
                ok, why = self.expr_static(sub, scopes, _depth + 1)
                if not ok:
                    return ok, why
            return True, ""
        if isinstance(e, ast.BinOp):
            for sub in (e.left, e.right):
                ok, why = self.expr_static(sub, scopes, _depth + 1)
                if not ok:
                    return ok, why
            return True, ""
        if isinstance(e, ast.IfExp):
            for sub in (e.body, e.orelse):
                ok, why = self.expr_static(sub, scopes, _depth + 1)
                if not ok:
                    return ok, why
            return True, ""
        if isinstance(e, (ast.Tuple, ast.List, ast.Set)):
            for sub in e.elts:
                ok, why = self.expr_static(sub, scopes, _depth + 1)
                if not ok:
                    return ok, why
            return True, ""
        if isinstance(e, ast.JoinedStr):
            return True, ""
        if isinstance(e, ast.Subscript):
            return self.expr_static(e.value, scopes, _depth + 1)
        if isinstance(e, ast.Starred):
            return self.expr_static(e.value, scopes, _depth + 1)
        return False, (f"a {type(e).__name__} expression — not "
                       "provably static")


# ---------------------------------------------------------------------------
# Site discovery
# ---------------------------------------------------------------------------

def _is_jit_call(call: ast.Call) -> bool:
    name = call_name(call)
    return name == "jit" or name.endswith(".jit")


def _is_partial_jit(call: ast.Call) -> bool:
    name = call_name(call)
    if not (name == "partial" or name.endswith(".partial")):
        return False
    return bool(call.args) and isinstance(call.args[0],
                                          (ast.Attribute, ast.Name)) \
        and _is_jit_name(call.args[0])


def _is_jit_name(node: ast.AST) -> bool:
    d = dotted(node)
    return d == "jit" or d.endswith(".jit")


def _registry_call(call: ast.Call) -> bool:
    name = call_name(call)
    if name in REGISTRY_CALLS:
        return True
    return any(name.endswith(sfx) for sfx in REGISTRY_CALL_SUFFIXES)


def _call_arg(call: ast.Call, idx: int, *kw_names: str
              ) -> Optional[ast.AST]:
    """Positional-or-keyword argument lookup — `get(key, builder=b)`
    and `jax.jit(fun=f)` must not silently bypass the audit."""
    if len(call.args) > idx:
        return call.args[idx]
    for kw in call.keywords:
        if kw.arg in kw_names:
            return kw.value
    return None


def _local_defs(sf: SourceFile, at: ast.AST, name: str
                ) -> List[ast.FunctionDef]:
    """Resolve ``name`` to FunctionDefs in the scopes enclosing ``at``
    (innermost scope wins). A name conditionally bound to several defs
    (if/else branches, two same-named builders in one method) returns
    ALL defs preceding the use — every one of them can be the runtime
    binding, so every one is audited."""
    for scope in sf.enclosing_functions(at) + [sf.tree]:
        cands: List[ast.FunctionDef] = []
        for node in ast.walk(scope):
            if isinstance(node, (ast.FunctionDef,
                                 ast.AsyncFunctionDef)) \
                    and node.name == name:
                # must belong to THIS scope, not a deeper function
                encl = sf.enclosing_functions(node)
                if (encl and encl[0] is scope) or (scope is sf.tree
                                                   and not encl):
                    cands.append(node)
        if cands:
            use_line = getattr(at, "lineno", 1 << 30)
            before = [c for c in cands if c.lineno <= use_line]
            return sorted(before or cands, key=lambda c: c.lineno)
    return []


def _key_covered_names(sf: SourceFile, call: ast.Call) -> Set[str]:
    """Names that are part of a registry call's KEY expression: a
    capture that is literally in the key cannot go stale across a
    registry hit — a different value is a different key, hence a
    different compiled program."""
    key = _call_arg(call, 0, "key")
    if key is None:
        return set()
    exprs: List[ast.AST] = []
    if isinstance(key, ast.Name):
        kinds = _Kinds(sf, set())
        for fn in sf.enclosing_functions(call):
            exprs.extend(kinds._bindings(fn, key.id))
            if exprs:
                break
    else:
        exprs.append(key)
    names: Set[str] = set()
    for e in exprs:
        if isinstance(e, ast.AST):
            for n in ast.walk(e):
                if isinstance(n, ast.Name):
                    names.add(n.id)
    return names


# ---------------------------------------------------------------------------
# Checker entry
# ---------------------------------------------------------------------------

def check(sources: List[SourceFile],
          config_fields: Set[str]) -> List[Finding]:
    out: List[Finding] = []
    for sf in sources:
        kinds = _Kinds(sf, config_fields)
        seen_fns: Set[int] = set()
        for node in ast.walk(sf.tree):
            if not isinstance(node, ast.Call):
                continue
            if _is_jit_call(node):
                target = _call_arg(node, 0, "fun")
                if target is not None:
                    _check_jit_target(sf, kinds, node, target,
                                      seen_fns, out)
            elif _registry_call(node):
                builder = _call_arg(node, 1, "builder")
                if builder is not None:
                    _check_registered_builder(sf, kinds, node,
                                              builder, seen_fns, out)
                elif _call_arg(node, 0, "key") is not None:
                    # a registration whose builder we cannot even
                    # locate must not pass silently
                    out.append(Finding(
                        CHECKER, "unresolvable-builder", sf.rel,
                        node.lineno,
                        f"{call_name(node)} call has no locatable "
                        "builder argument (positional #2 or "
                        "builder=) — the registered program cannot "
                        "be audited",
                        f"{sf.qualname(node)}:{call_name(node)}"))
        # decorated defs: @jax.jit / @partial(jax.jit, ...)
        for node in ast.walk(sf.tree):
            if isinstance(node, (ast.FunctionDef,
                                 ast.AsyncFunctionDef)):
                for dec in node.decorator_list:
                    if _is_jit_name(dec) or (
                            isinstance(dec, ast.Call)
                            and (_is_jit_call(dec)
                                 or _is_partial_jit(dec))):
                        _check_function(sf, kinds, node, node,
                                        seen_fns, out,
                                        registry=False)
    return out


def _check_jit_target(sf: SourceFile, kinds: _Kinds, call: ast.Call,
                      target: ast.AST, seen: Set[int],
                      out: List[Finding]) -> None:
    if isinstance(target, ast.Lambda):
        _check_function(sf, kinds, target, call, seen, out,
                        registry=False)
        return
    if isinstance(target, ast.Name):
        fns = _local_defs(sf, call, target.id)
        if fns:
            for fn in fns:
                _check_function(sf, kinds, fn, call, seen, out,
                                registry=False)
            return
        # a module-level def jitted by name has no frees — find it
        waivers = _parse_waivers(sf.comment_near(call))
        if waivers is not None and waivers.covers(target.id, True):
            return
        out.append(Finding(
            CHECKER, "unresolvable", sf.rel, call.lineno,
            f"jax.jit target {target.id!r} does not resolve to a "
            "local function — captures cannot be audited; waive with "
            f"'# jit-capture: ok({target.id}) — reason' if its "
            "capture discipline is established elsewhere",
            f"{sf.qualname(call)}:{target.id}"))
        return
    # jit of an arbitrary expression (e.g. jax.jit(jax.shard_map(...)))
    waivers = _parse_waivers(sf.comment_near(call))
    if waivers is not None and waivers.wildcard:
        return
    expr = ast.unparse(target)
    out.append(Finding(
        CHECKER, "unresolvable", sf.rel, call.lineno,
        f"jax.jit of a non-name expression ({expr[:48]}) — captures "
        "cannot be audited; waive with '# jit-capture: ok(*) — reason'",
        f"{sf.qualname(call)}:{expr[:48]}"))


def _check_registered_builder(sf: SourceFile, kinds: _Kinds,
                              call: ast.Call, builder: ast.AST,
                              seen: Set[int],
                              out: List[Finding]) -> None:
    reg = call_name(call)
    key_names = _key_covered_names(sf, call)
    if isinstance(builder, ast.Lambda):
        _check_function(sf, kinds, builder, call, seen, out,
                        registry=True, key_names=key_names)
        return
    if not isinstance(builder, ast.Name):
        out.append(Finding(
            CHECKER, "unresolvable-builder", sf.rel, call.lineno,
            f"{reg} builder is not a simple local function — the "
            "registered program's captures cannot be audited",
            f"{sf.qualname(call)}:{ast.unparse(builder)[:48]}"))
        return
    fns = _local_defs(sf, call, builder.id)
    if not fns:
        waivers = _parse_waivers(sf.comment_near(call))
        if waivers is not None and waivers.covers(builder.id, False):
            return
        out.append(Finding(
            CHECKER, "unresolvable-builder", sf.rel, call.lineno,
            f"{reg} builder {builder.id!r} does not resolve to a "
            "local function; waive with '# jit-capture: "
            f"ok({builder.id}) — reason' (named waivers only for "
            "registry registrations)",
            f"{sf.qualname(call)}:{builder.id}"))
        return
    # the REGISTERED value is what the builder returns: audit every
    # returned local function; returns of audited factories delegate
    for fn in fns:
        for ret in ast.walk(fn):
            if not isinstance(ret, ast.Return) or ret.value is None:
                continue
            if sf.enclosing_functions(ret)[0] is not fn:
                continue                # a nested function's return
            v = ret.value
            if isinstance(v, ast.Name):
                inners = _local_defs(sf, ret, v.id)
                if inners:
                    for inner in inners:
                        _check_function(sf, kinds, inner, call, seen,
                                        out, registry=True,
                                        key_names=key_names)
                    continue
            if isinstance(v, ast.Call) and \
                    call_name(v) in AUDITED_BUILDER_FACTORIES:
                continue                # audited at the factory's site
            if isinstance(v, ast.Call) and _is_jit_call(v) and v.args:
                # ``return jax.jit(step)`` — the registered program is
                # the jitted local function, audited REGISTRY-strict
                tgt = v.args[0]
                inners = (_local_defs(sf, ret, tgt.id)
                          if isinstance(tgt, ast.Name) else
                          [tgt] if isinstance(tgt, ast.Lambda) else [])
                if inners:
                    for inner in inners:
                        seen.discard(id(inner))   # registry-strict wins
                        _check_function(sf, kinds, inner, call, seen,
                                        out, registry=True,
                                        key_names=key_names)
                    continue
            if isinstance(v, ast.Lambda):
                _check_function(sf, kinds, v, call, seen, out,
                                registry=True, key_names=key_names)
                continue
            out.append(Finding(
                CHECKER, "unresolvable-builder", sf.rel, ret.lineno,
                f"builder {fn.name!r} (registered via {reg}) returns "
                f"{ast.unparse(v)[:48]!r} — not a local function or "
                "an audited factory; the registered program's "
                "captures cannot be audited",
                f"{sf.qualname(fn)}:{ast.unparse(v)[:48]}"))


def _check_function(sf: SourceFile, kinds: _Kinds, fn: ast.AST,
                    site: ast.AST, seen: Set[int],
                    out: List[Finding], registry: bool,
                    key_names: frozenset = frozenset()) -> None:
    if id(fn) in seen:
        return
    seen.add(id(fn))
    frees = sf.free_names(fn)
    if not frees:
        return
    waivers = _parse_waivers(sf.comment_near(fn),
                             sf.comment_near(site))
    scopes = sf.enclosing_functions(fn)
    qual = sf.qualname(fn)
    kind_word = "registered in the process-wide registry" if registry \
        else "jitted"
    for name in frees:
        if name in key_names:
            continue        # literally part of the registry key:
            #                 a different value is a different program
        if waivers is not None and \
                waivers.covers(name, allow_wildcard=not registry):
            continue
        ok, why = kinds.classify_free(name, scopes)
        if ok:
            continue
        hint = "named waivers only — this program outlives the " \
               "booster that built it" if registry else \
               f"'# jit-capture: ok({name}) — reason' waives it"
        out.append(Finding(
            CHECKER, "nonstatic-capture", sf.rel,
            getattr(fn, "lineno", site.lineno),
            f"{qual} is {kind_word} but closes over {name!r}: {why}; "
            f"pass it as a traced argument ({hint})",
            f"{qual}:{name}"))

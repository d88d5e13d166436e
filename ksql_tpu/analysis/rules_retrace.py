"""jit-retrace rule: patterns that force XLA recompiles or per-call traces.

Every perf direction in ROADMAP (sliced windows, distributed parity)
lives or dies on avoiding silent recompilation —
and until PR 8 the only signal was the ``jit_miss`` counter AFTER the
throughput had already collapsed.  This rule shifts the bug class left,
flagging inside the jit-traced call tree (``_trace_*`` functions, ``@jit``
-decorated defs, and the module-local helpers they call, with parameter
taint propagated call-site -> callee to a bounded depth):

* **branch-on-tracer** — a Python ``if``/``while`` whose test derives
  from traced values: either a trace error at runtime or, with shape
  polymorphism, a silent retrace per branch flip.  ``x is None`` /
  ``isinstance`` tests are exempt (Optional plumbing is resolved at trace
  time).
* **concretization** — ``int()`` / ``float()`` / ``bool()`` / ``.item()``
  / ``.tolist()`` on traced values: forces a host sync (or a trace
  error), and as a ``jax.jit`` static argument it recompiles per value.
* **host-string of tracer** — f-strings / ``str()`` / ``repr()`` over
  traced values bake the trace-time abstract value into a string.
* **mutable-host capture** — a traced body reading ``self.<attr>`` that
  some host-side method mutates WITHOUT triggering a recompile (the
  mutator neither runs at construction time nor reaches a
  ``*compile*`` call): the trace keeps the stale snapshot forever.
  Mutators that recompile (``_resize_ring`` -> ``_compile_steps``) are
  the repo's sanctioned pattern and stay silent.
* **per-batch static arg** — a call to a ``jax.jit(...,
  static_argnums=...)`` binding passing, at a static position, an
  unhashable literal (TypeError at call time), an f-string, or a value
  derived from the calling function's own parameters (``len(rows)``,
  ``arr.shape[0]``): a new compile cache entry per distinct batch.
"""

from __future__ import annotations

import ast
from typing import Dict, Iterable, List, Optional, Set, Tuple

from ksql_tpu.analysis.lint import (
    Finding,
    LintModule,
    Rule,
    call_name,
    dotted_name,
)

_JIT_NAMES = ("jax.jit", "jit")
_CONCRETIZERS = {"int", "float", "bool"}
_CONCRETIZER_METHODS = {"item", "tolist"}
_STRINGIFIERS = {"str", "repr", "format"}
_TRACE_DEPTH = 3
#: mutator functions containing/reaching these name fragments are the
#: sanctioned mutate-then-recompile pattern, not a stale capture
_RECOMPILE_MARKERS = ("compile", "build_steps", "rebuild")


def _decorated_jit(fn: ast.FunctionDef) -> bool:
    for dec in fn.decorator_list:
        name = dotted_name(dec)
        if name in _JIT_NAMES:
            return True
        if isinstance(dec, ast.Call):
            cname = call_name(dec)
            if cname in _JIT_NAMES:
                return True
            if cname in ("partial", "functools.partial") and dec.args:
                if dotted_name(dec.args[0]) in _JIT_NAMES:
                    return True
    return False


def _static_positions(call: ast.Call) -> Set[int]:
    """Literal static_argnums positions only.  Anything unparseable —
    static_argnames (string-keyed, no position mapping without the
    callee's signature), a variable, a computed tuple — yields NO
    positions: guessing {0} would flag correct code, and this rule's
    contract is that resolution failures cost recall, never precision."""
    for kw in call.keywords:
        if kw.arg != "static_argnums":
            continue
        v = kw.value
        if isinstance(v, ast.Constant) and isinstance(v.value, int):
            return {v.value}
        if isinstance(v, ast.Tuple):
            return {
                e.value for e in v.elts
                if isinstance(e, ast.Constant) and isinstance(e.value, int)
            }
        return set()
    return set()


class _ModuleView:
    """Traced-set discovery + light parameter taint for one module."""

    def __init__(self, module: LintModule):
        self.module = module
        self.fns = module.functions()
        self.by_name: Dict[str, List[ast.FunctionDef]] = {}
        for fn in self.fns:
            self.by_name.setdefault(fn.name, []).append(fn)
        #: jitted binding name ("self._step", "_step") -> static positions
        self.static_bindings: Dict[str, Set[int]] = {}
        for node in ast.walk(module.tree):
            if not isinstance(node, ast.Assign):
                continue
            for target in node.targets:
                key = dotted_name(target)
                if key is None:
                    continue
                for call in ast.walk(node.value):
                    if isinstance(call, ast.Call) \
                            and call_name(call) in _JIT_NAMES:
                        pos = _static_positions(call)
                        if pos:
                            self.static_bindings[key] = pos
        #: fn id -> set of tainted (tracer-carrying) parameter names
        self.tainted_params: Dict[int, Set[str]] = {}
        self.traced: List[ast.FunctionDef] = []
        self._discover()
        self._init_reach = self._reach_from_inits()

    # ------------------------------------------------------------ traced
    def _roots(self) -> List[ast.FunctionDef]:
        return [
            fn for fn in self.fns
            if fn.name.startswith("_trace_") or _decorated_jit(fn)
        ]

    def _local_callee(self, fn: ast.FunctionDef,
                      name: str) -> Optional[ast.FunctionDef]:
        parts = name.split(".")
        if len(parts) > 2 or (len(parts) == 2
                              and parts[0] not in ("self", "cls")):
            return None
        cands = self.by_name.get(parts[-1], [])
        return cands[0] if cands else None

    def _discover(self) -> None:
        """Traced set = roots + local callees to depth 3, with parameter
        taint pushed call-site -> callee (two passes settle chains)."""
        traced: Dict[int, ast.FunctionDef] = {}
        for fn in self._roots():
            traced[id(fn)] = fn
            self.tainted_params[id(fn)] = {
                a.arg for a in fn.args.args
                if a.arg not in ("self", "cls")
                and not _static_param(fn, a)
            }
        for _ in range(2):
            frontier = list(traced.values())
            for _depth in range(_TRACE_DEPTH):
                nxt: List[ast.FunctionDef] = []
                for fn in frontier:
                    env = self.tainted_params.get(id(fn), set())
                    for node in ast.walk(fn):
                        if not isinstance(node, ast.Call):
                            continue
                        name = call_name(node)
                        if name is None:
                            continue
                        callee = self._local_callee(fn, name)
                        if callee is None or callee.name.startswith(
                            "__"
                        ):
                            continue
                        shift = 1 if callee.args.args and \
                            callee.args.args[0].arg in ("self", "cls") \
                            and "." in name else 0
                        tp = self.tainted_params.setdefault(
                            id(callee), set()
                        )
                        for i, arg in enumerate(node.args):
                            pi = i + shift
                            if pi < len(callee.args.args) and \
                                    _expr_tainted(arg, env):
                                tp.add(callee.args.args[pi].arg)
                        if id(callee) not in traced:
                            traced[id(callee)] = callee
                            nxt.append(callee)
                frontier = nxt
        self.traced = list(traced.values())

    # ----------------------------------------------- construction excusal
    def _reach_from_inits(self) -> Set[int]:
        seen: Set[int] = set()
        frontier = [fn for fn in self.fns if fn.name == "__init__"]
        seen |= {id(fn) for fn in frontier}
        for _ in range(_TRACE_DEPTH):
            nxt = []
            for fn in frontier:
                for node in ast.walk(fn):
                    if isinstance(node, ast.Call):
                        name = call_name(node)
                        callee = (
                            self._local_callee(fn, name)
                            if name is not None else None
                        )
                        if callee is not None and id(callee) not in seen:
                            seen.add(id(callee))
                            nxt.append(callee)
            frontier = nxt
        return seen

    def _triggers_recompile(self, fn: ast.FunctionDef,
                            depth: int = _TRACE_DEPTH) -> bool:
        if any(m in fn.name.lower() for m in _RECOMPILE_MARKERS):
            return True
        if depth <= 0:
            return False
        for node in ast.walk(fn):
            if isinstance(node, ast.Delete):
                # `del self._fk_steps`: the lazy-rebuild recompile idiom —
                # dropping the compiled-steps cache forces a fresh trace
                # on next use
                for t in node.targets:
                    if isinstance(t, ast.Attribute) and "step" in t.attr:
                        return True
                continue
            if not isinstance(node, ast.Call):
                continue
            name = call_name(node)
            if name is None:
                continue
            if any(m in name.lower() for m in _RECOMPILE_MARKERS):
                return True
            if name in _JIT_NAMES:
                return True  # re-jits the step in place: a fresh trace
            callee = self._local_callee(fn, name)
            if callee is not None and callee is not fn \
                    and self._triggers_recompile(callee, depth - 1):
                return True
        return False

    def stale_capture_attrs(self) -> Set[str]:
        """self attributes some host-side method mutates without either
        running at construction time or triggering a recompile — reading
        one inside the traced tree captures a stale snapshot."""
        traced_ids = {id(fn) for fn in self.traced}
        out: Set[str] = set()
        for fn in self.fns:
            if id(fn) in traced_ids or id(fn) in self._init_reach:
                continue
            if fn.name.startswith("__") or self._triggers_recompile(fn):
                continue
            for node in ast.walk(fn):
                targets: List[ast.AST] = []
                if isinstance(node, ast.Assign):
                    targets = list(node.targets)
                elif isinstance(node, (ast.AugAssign, ast.AnnAssign)):
                    targets = [node.target]
                for t in targets:
                    if isinstance(t, ast.Attribute) \
                            and isinstance(t.value, ast.Name) \
                            and t.value.id == "self":
                        out.add(t.attr)
        return out


def _static_param(fn: ast.FunctionDef, arg: ast.arg) -> bool:
    """Trace-root parameters that are trace-time STATICS by this repo's
    binding idiom: scalar-annotated (``side: str`` / ``idx: int`` bound
    via closure defaults in _compile_steps lambdas) or carrying a scalar
    constant default."""
    ann = arg.annotation
    if isinstance(ann, ast.Name) and ann.id in (
        "int", "str", "bool", "float"
    ):
        return True
    args = fn.args
    defaults = args.defaults
    if defaults:
        offset = len(args.args) - len(defaults)
        try:
            i = args.args.index(arg)
        except ValueError:
            return False
        if i >= offset and isinstance(defaults[i - offset], ast.Constant):
            return True
    return False


def _expr_tainted(expr: ast.AST, env: Set[str]) -> bool:
    for n in ast.walk(expr):
        if isinstance(n, ast.Name) and n.id in env:
            return True
    return False


def _test_exempt(test: ast.AST) -> bool:
    """Tests resolved at trace time even over traced operands: identity
    against None, isinstance, and boolean combinations thereof."""
    if isinstance(test, ast.UnaryOp) and isinstance(test.op, ast.Not):
        return _test_exempt(test.operand)
    if isinstance(test, ast.BoolOp):
        return all(_test_exempt(v) for v in test.values)
    if isinstance(test, ast.Compare):
        if all(isinstance(op, (ast.Is, ast.IsNot)) for op in test.ops):
            return True
        # `"key" in store`: pytree STRUCTURE membership, fixed at trace
        # time (tracers live in the values, the key set is static)
        return (
            all(isinstance(op, (ast.In, ast.NotIn)) for op in test.ops)
            and isinstance(test.left, ast.Constant)
        )
    if isinstance(test, ast.Call):
        return call_name(test) in ("isinstance", "hasattr", "len")
    if isinstance(test, ast.Attribute) or isinstance(test, ast.Constant):
        return True  # self.flag / literal: trace-time static
    return False


class JitRetraceRule(Rule):
    name = "jit-retrace"
    doc = ("no Python branches/concretization/f-strings on traced values, "
           "no stale mutable-host capture, no per-batch static args — "
           "each forces an XLA recompile or per-call retrace")

    def check(self, module: LintModule) -> Iterable[Finding]:
        view = _ModuleView(module)
        out: List[Finding] = []
        if view.traced:
            stale = view.stale_capture_attrs()
            for fn in view.traced:
                out.extend(self._check_traced(module, view, fn, stale))
        if view.static_bindings:
            out.extend(self._check_static_calls(module, view))
        # deduplicate across overlapping traced walks
        seen: Set[Tuple[int, int, str]] = set()
        uniq = []
        for f in out:
            k = (f.line, f.col, f.message)
            if k not in seen:
                seen.add(k)
                uniq.append(f)
        return uniq

    def _finding(self, module: LintModule, node: ast.AST,
                 msg: str) -> Finding:
        return Finding(self.name, module.path, node.lineno,
                       node.col_offset, msg)

    # ------------------------------------------------------- traced body
    def _check_traced(self, module: LintModule, view: _ModuleView,
                      fn: ast.FunctionDef, stale: Set[str]) -> List[Finding]:
        out: List[Finding] = []
        env = set(view.tainted_params.get(id(fn), set()))
        # forward pass: taint assignments derived from tainted names.
        # Only the target ROOT is tainted — `jt[f"v_{col.name}"] = x`
        # taints jt, never the index expression's names
        def roots(t: ast.AST) -> Iterable[str]:
            if isinstance(t, (ast.Tuple, ast.List)):
                for e in t.elts:
                    yield from roots(e)
                return
            while isinstance(t, (ast.Subscript, ast.Attribute, ast.Starred)):
                t = t.value
            if isinstance(t, ast.Name):
                yield t.id

        for node in ast.walk(fn):
            if isinstance(node, ast.Assign) and _expr_tainted(
                node.value, env
            ):
                for t in node.targets:
                    env.update(roots(t))
        for node in ast.walk(fn):
            if isinstance(node, (ast.If, ast.While)):
                if _expr_tainted(node.test, env) \
                        and not _test_exempt(node.test):
                    out.append(self._finding(
                        module, node,
                        f"Python branch on a traced value in {fn.name}: "
                        "tracer boolean coercion fails or silently "
                        "retraces per flip — use jnp.where/lax.cond",
                    ))
            elif isinstance(node, ast.Call):
                name = call_name(node)
                if name in _CONCRETIZERS and node.args and _expr_tainted(
                    node.args[0], env
                ):
                    out.append(self._finding(
                        module, node,
                        f"{name}() concretizes a traced value in "
                        f"{fn.name}: host sync / trace error — and as a "
                        "static arg it recompiles per value",
                    ))
                elif name in _STRINGIFIERS and node.args \
                        and _expr_tainted(node.args[0], env):
                    out.append(self._finding(
                        module, node,
                        f"{name}() over a traced value in {fn.name} "
                        "bakes the trace-time abstract value into a "
                        "string",
                    ))
                elif isinstance(node.func, ast.Attribute) \
                        and node.func.attr in _CONCRETIZER_METHODS \
                        and _expr_tainted(node.func.value, env):
                    out.append(self._finding(
                        module, node,
                        f".{node.func.attr}() on a traced value in "
                        f"{fn.name}: forces a device sync per call (or "
                        "fails under jit)",
                    ))
            elif isinstance(node, ast.JoinedStr):
                for v in node.values:
                    if isinstance(v, ast.FormattedValue) \
                            and _expr_tainted(v.value, env):
                        out.append(self._finding(
                            module, node,
                            f"f-string over a traced value in {fn.name}: "
                            "bakes the trace-time abstract value into a "
                            "string (shape-derived strings vary per "
                            "batch and force retraces as static args)",
                        ))
                        break
            elif isinstance(node, ast.Attribute) \
                    and isinstance(node.ctx, ast.Load) \
                    and isinstance(node.value, ast.Name) \
                    and node.value.id == "self" \
                    and node.attr in stale:
                out.append(self._finding(
                    module, node,
                    f"traced {fn.name} reads mutable host state "
                    f"'self.{node.attr}' (mutated by a non-recompiling "
                    "host path): the compiled step keeps the trace-time "
                    "snapshot forever — pass it as an argument or "
                    "recompile on mutation",
                ))
        return out

    # -------------------------------------------------- static-arg calls
    def _check_static_calls(self, module: LintModule,
                            view: _ModuleView) -> List[Finding]:
        out: List[Finding] = []
        for fn in module.functions():
            params = {
                a.arg for a in fn.args.args if a.arg not in ("self", "cls")
            }
            for node in ast.walk(fn):
                if not isinstance(node, ast.Call):
                    continue
                name = call_name(node)
                positions = None
                if name is not None:
                    positions = view.static_bindings.get(name)
                    if positions is None and name.startswith("self."):
                        positions = view.static_bindings.get(
                            name.split(".", 1)[1]
                        )
                if not positions:
                    continue
                for pos in sorted(positions):
                    if pos >= len(node.args):
                        continue
                    arg = node.args[pos]
                    if isinstance(arg, (ast.List, ast.Dict, ast.Set)):
                        out.append(self._finding(
                            module, node,
                            f"unhashable literal at static position "
                            f"{pos} of jitted '{name}': TypeError at "
                            "call time — static args must be hashable",
                        ))
                    elif any(isinstance(n, ast.JoinedStr)
                             for n in ast.walk(arg)):
                        out.append(self._finding(
                            module, node,
                            f"f-string at static position {pos} of "
                            f"jitted '{name}': a distinct string per "
                            "call means a silent recompile per call",
                        ))
                    elif _expr_tainted(arg, params):
                        out.append(self._finding(
                            module, node,
                            f"static position {pos} of jitted '{name}' "
                            "derives from the caller's per-batch data: "
                            "every distinct value compiles a new XLA "
                            "program (the jit_miss counter you see "
                            "after the fact)",
                        ))
        return out

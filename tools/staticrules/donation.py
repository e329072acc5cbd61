"""BTF002 — no reads of a donated buffer after the dispatch that donated it.

Past incident class: every decode/prefill/spec dispatch donates the KV
pools (and the spec block donates the device token-history carry; the
write-combined windowed blocks additionally donate the staged-window
buffer + per-slot staged count — ISSUE 12's window carry — and the
mixed-dispatch blocks donate the per-slot prefill CURSOR carry —
ISSUE 18's chunk-offset vector, rebound from every mixed_block_async
/ mixed_spec_block_async result; all the same factory pattern) so XLA
updates them in place. A host-side read of the donated reference
after the dispatch call observes freed/aliased memory — under paged
serving this aliases garbage K/V under a valid page id, silently
(PR 5's "in-flight writes must never land on reclaimed pages" is the
scheduler-level twin of the same hazard; PR 6's geometry-mismatch 409
is the cross-replica one).

Mechanics (per function, linear flow with loop bodies walked twice so a
next-iteration read is seen):

* donating callables are discovered from ``self.X = jax.jit(...,
  donate_argnums=...)`` assignments, from factory methods that build and
  return such a jit (``self._mixed_block_prog(k, C, P)(...)`` and
  ``verify = self._verify_program(...)``), from ``A if c else B``
  aliases of two same-signature donators, and from the
  ``KNOWN_DONATING_METHODS`` table for cross-module engine APIs whose
  docstring-contract donates a caller argument.
* at a donating call, every donated positional arg that is a plain
  reference (``cache``, ``self.cache``, ``self._hist_dev``) is poisoned
  — unless the same statement rebinds it (the blessed
  ``logits, cache = prog(..., cache, ...)`` pattern).
* any later read of a poisoned reference is a finding; any store to it
  clears the poison.
"""
from __future__ import annotations

import ast
from typing import Dict, Iterator, List, Optional, Set, Tuple

from . import (FileContext, Finding, Rule, assigned_handles, handle_of,
               register)

#: Cross-module donating APIs: method name -> donated positional indices
#: OF THE CALLER'S argument list (engine/serving.py's jit
#: donate_argnums shifted past the bound params); cast_params donates
#: the source tree.
#: The mixed-dispatch blocks (ISSUE 18) donate the per-slot prefill
#: cursor carry — mixed_block_async its ``cursor`` (caller index 1),
#: mixed_spec_block_async its ``hist`` and ``cursor`` (0 and 2); the
#: prompt buffer is deliberately NOT donated (the scheduler edits it
#: host-side between dispatches at admission).
#: What a method donates of the engine's own (self.cache, the window)
#: is no caller argument and is absent by design.
KNOWN_DONATING_METHODS: Dict[str, Tuple[int, ...]] = {
    "mixed_block_async": (1,),
    "mixed_spec_block_async": (0, 2),
    "cast_params": (0,),
}


def _donate_argnums(call: ast.Call) -> Optional[Tuple[int, ...]]:
    """(indices,) iff `call` is jax.jit(..., donate_argnums=...)."""
    func = call.func
    is_jit = (isinstance(func, ast.Attribute) and func.attr == "jit") or \
             (isinstance(func, ast.Name) and func.id == "jit")
    if not is_jit:
        return None
    for kw in call.keywords:
        if kw.arg != "donate_argnums":
            continue
        v = kw.value
        if isinstance(v, ast.Constant) and isinstance(v.value, int):
            return (v.value,)
        if isinstance(v, (ast.Tuple, ast.List)):
            out = []
            for e in v.elts:
                if isinstance(e, ast.Constant) and isinstance(e.value, int):
                    out.append(e.value)
            return tuple(out)
        return ()  # dynamic indices: can't track, treat as non-donating
    return None


class _ClassTable:
    """Donating callables reachable through ``self`` in one class."""

    def __init__(self):
        self.attrs: Dict[str, Tuple[int, ...]] = {}      # self.X(...)
        self.factories: Dict[str, Tuple[int, ...]] = {}  # self.F(...)(...)


def _collect_class_tables(tree: ast.AST) -> Dict[ast.ClassDef, _ClassTable]:
    tables: Dict[ast.ClassDef, _ClassTable] = {}
    for node in ast.walk(tree):
        if not isinstance(node, ast.ClassDef):
            continue
        table = _ClassTable()
        for meth in node.body:
            if not isinstance(meth, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            jit_indices: Optional[Tuple[int, ...]] = None
            has_return = False
            for sub in ast.walk(meth):
                if isinstance(sub, ast.Call):
                    idx = _donate_argnums(sub)
                    if idx:
                        jit_indices = idx
                if isinstance(sub, ast.Return) and sub.value is not None:
                    has_return = True
                # self.X = jax.jit(..., donate_argnums=...)
                if isinstance(sub, ast.Assign) and \
                        isinstance(sub.value, ast.Call):
                    idx = _donate_argnums(sub.value)
                    if idx:
                        for t in sub.targets:
                            if isinstance(t, ast.Attribute) and \
                                    isinstance(t.value, ast.Name) and \
                                    t.value.id == "self":
                                table.attrs[t.attr] = idx
            # a method that builds a donating jit and returns something
            # is a program factory (the _mixed_block_prog /
            # _verify_program caching pattern)
            if jit_indices and has_return:
                table.factories[meth.name] = jit_indices
        tables[node] = table
    return tables


class _FunctionFlow:
    """Linear poison-propagation over one function body."""

    def __init__(self, rule: "UseAfterDonationRule", ctx: FileContext,
                 table: _ClassTable):
        self.rule = rule
        self.ctx = ctx
        self.table = table
        self.findings: List[Finding] = []
        self._seen: Set[Tuple[int, int, str]] = set()
        #: locals bound to a donating callable: V = self._verify_program(...)
        self.local_donators: Dict[str, Tuple[int, ...]] = {}

    # -- donating-call discovery ------------------------------------------

    def _call_donates(self, call: ast.Call) -> Optional[Tuple[int, ...]]:
        func = call.func
        # self.X(...) where X is a recorded donating jit attribute
        if isinstance(func, ast.Attribute) and \
                isinstance(func.value, ast.Name) and func.value.id == "self":
            if func.attr in self.table.attrs:
                return self.table.attrs[func.attr]
        # V(...) where V was bound to a factory's product
        if isinstance(func, ast.Name) and func.id in self.local_donators:
            return self.local_donators[func.id]
        # self.F(...)(...) — factory called inline
        if isinstance(func, ast.Call) and \
                isinstance(func.func, ast.Attribute) and \
                isinstance(func.func.value, ast.Name) and \
                func.func.value.id == "self":
            if func.func.attr in self.table.factories:
                return self.table.factories[func.func.attr]
        # cross-module engine APIs donating a caller argument
        if isinstance(func, ast.Attribute) and \
                func.attr in KNOWN_DONATING_METHODS:
            return KNOWN_DONATING_METHODS[func.attr]
        if isinstance(func, ast.Name) and \
                func.id in KNOWN_DONATING_METHODS:
            return KNOWN_DONATING_METHODS[func.id]
        return None

    def _donated_handles(self, stmt: ast.stmt) -> Set[str]:
        out: Set[str] = set()
        for node in ast.walk(stmt):
            if not isinstance(node, ast.Call):
                continue
            indices = self._call_donates(node)
            if not indices:
                continue
            for i in indices:
                if i < len(node.args):
                    h = handle_of(node.args[i])
                    if h and h != "self":
                        out.add(h)
        return out

    def _note_donator_aliases(self, stmt: ast.stmt) -> None:
        """Track V = self._verify_program(...) / V = self._a if c else
        self._b (both donators) so later V(...) calls are donating."""
        if not isinstance(stmt, ast.Assign) or len(stmt.targets) != 1:
            return
        t = stmt.targets[0]
        if not isinstance(t, ast.Name):
            return
        v = stmt.value
        if isinstance(v, ast.Call):
            idx = _donate_argnums(v)
            if idx:  # V = jax.jit(..., donate_argnums=...) in-function
                self.local_donators[t.id] = idx
                return
        if isinstance(v, ast.Call) and isinstance(v.func, ast.Attribute) \
                and isinstance(v.func.value, ast.Name) \
                and v.func.value.id == "self" \
                and v.func.attr in self.table.factories:
            self.local_donators[t.id] = self.table.factories[v.func.attr]
            return
        if isinstance(v, ast.IfExp):
            def attr_of(e):
                if isinstance(e, ast.Attribute) and \
                        isinstance(e.value, ast.Name) and \
                        e.value.id == "self":
                    return self.table.attrs.get(e.attr)
                return None
            a, b = attr_of(v.body), attr_of(v.orelse)
            if a is not None and a == b:
                self.local_donators[t.id] = a

    # -- reads --------------------------------------------------------------

    def _flag_reads(self, node: ast.AST, poison: Set[str]) -> None:
        if not poison:
            return
        for sub in ast.walk(node):
            if isinstance(sub, (ast.Name, ast.Attribute)) and \
                    isinstance(getattr(sub, "ctx", None), ast.Load):
                h = handle_of(sub)
                if h in poison:
                    key = (sub.lineno, sub.col_offset, h)
                    if key in self._seen:
                        continue
                    self._seen.add(key)
                    self.findings.append(self.rule.finding(
                        self.ctx, sub,
                        f"read of {h!r} after it was donated to a jit "
                        f"dispatch — the buffer may already be freed or "
                        f"aliased in place; rebind it from the call's "
                        f"result instead"))

    # -- flow ---------------------------------------------------------------

    def run(self, body: List[ast.stmt]) -> None:
        self._block(body, set())

    def _block(self, stmts: List[ast.stmt], poison: Set[str]) -> Set[str]:
        for stmt in stmts:
            poison = self._stmt(stmt, poison)
        return poison

    def _stmt(self, stmt: ast.stmt, poison: Set[str]) -> Set[str]:
        if isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef,
                             ast.ClassDef)):
            return poison  # nested scopes analyzed separately
        if isinstance(stmt, ast.If):
            self._flag_reads(stmt.test, poison)
            p1 = self._block(stmt.body, set(poison))
            p2 = self._block(stmt.orelse, set(poison))
            return p1 | p2
        if isinstance(stmt, (ast.For, ast.AsyncFor, ast.While)):
            header = stmt.iter if isinstance(stmt, (ast.For, ast.AsyncFor)) \
                else stmt.test
            self._flag_reads(header, poison)
            poison = poison - assigned_handles(stmt)
            # twice: a handle donated in iteration t is read at the top
            # of iteration t+1 — the single-pass walk would miss it
            for _ in range(2):
                poison = self._block(stmt.body, poison)
            return self._block(stmt.orelse, poison)
        if isinstance(stmt, (ast.With, ast.AsyncWith)):
            for item in stmt.items:
                self._flag_reads(item.context_expr, poison)
            return self._block(stmt.body, poison)
        if isinstance(stmt, ast.Try):
            poison = self._block(stmt.body, poison)
            merged = set(poison)
            for h in stmt.handlers:
                merged |= self._block(h.body, set(poison))
            merged = self._block(stmt.orelse, merged)
            return self._block(stmt.finalbody, merged)
        # simple statement: reads against the CURRENT poison set, then
        # new donations, then same-statement rebinds clear
        self._flag_reads(stmt, poison)
        self._note_donator_aliases(stmt)
        poison = poison | self._donated_handles(stmt)
        return poison - assigned_handles(stmt)


@register
class UseAfterDonationRule(Rule):
    id = "BTF002"
    name = "use-after-donation"
    invariant = ("a reference passed at a donate_argnums position is "
                 "never read after the dispatch unless rebound from the "
                 "call's result")
    scope = ("butterfly_tpu/engine/serving.py",
             "butterfly_tpu/engine/engine.py",
             "butterfly_tpu/sched/scheduler.py")

    def check(self, ctx: FileContext) -> Iterator[Finding]:
        tables = _collect_class_tables(ctx.tree)
        # map each function to its enclosing class's table (module-level
        # functions get an empty table: KNOWN methods still apply)
        empty = _ClassTable()
        owner: Dict[ast.AST, _ClassTable] = {}
        for cls, table in tables.items():
            for node in ast.walk(cls):
                if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                    owner.setdefault(node, table)
        for node in ast.walk(ctx.tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                flow = _FunctionFlow(self, ctx, owner.get(node, empty))
                flow.run(node.body)
                yield from flow.findings

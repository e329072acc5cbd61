"""BTF003 — no host synchronization inside the dispatch hot path.

Past incident class: a serving loop an order of magnitude slower than
the isolated decode on the same chip, because it was host-bound — every
per-token host<->device round trip (``int(np.asarray(tok))`` and
friends) serialized the device behind the host section (ROADMAP A1:
502 against 6,988 tok/s on the r5 machine). PRs 3/5/9 rebuilt the tick
around dispatch-ahead precisely so the HOT functions (tick, operand
assembly, block dispatch) never materialize a device value; draining is
where synchronization is *intended* and the drain functions are
deliberately outside this rule's hot set.

The rule flags, inside the configured hot functions only:

* ``.item()`` / ``.tolist()`` / ``.block_until_ready()`` calls — the
  unambiguous sync markers;
* ``jax.device_get(...)``;
* ``np.asarray(x)`` / ``np.array(x)`` where ``x`` is not host-side by
  construction (a list/tuple/comprehension/constant, or a parameter
  annotated as a host container like ``slots: list[int]``, is
  host->host and fine — the operand-assembly pattern);
* ``int()`` / ``float()`` / ``bool()`` whose argument mentions a
  device-carry name (``*_dev``, or one of the conventional
  device-resident names below) — the exact ``int(logits[...])`` shape
  the old per-token readback used.
"""
from __future__ import annotations

import ast
from typing import Iterator, Set

from . import FileContext, Finding, Rule, call_name, dotted_name, register

#: functions whose bodies must stay sync-free. Drain/emit functions are
#: intentionally absent: the drain's one `jax.device_get` over the
#: drained blocks' own outputs is the one blessed fetch.
#: The ISSUE 15 tick-anatomy paths (phase timers, the ticklog ring
#: append, the flight-recorder note/poll) run once per tick inside the
#: hot section, so they are IN the set: a timer that materialized a
#: device value would reintroduce exactly the sync it exists to find.
HOT_FUNCTIONS: Set[str] = {
    "tick", "_tick_sections", "_mixed_block",
    "_enqueue_block", "_assemble", "_admit_inline",
    "_seed_mixed_slot", "_finish_prefill",
    "_lap", "_starve", "_fed", "_note_fetch",
    "mixed_block_async", "mixed_spec_block_async",
    "_sync_table",
    # ISSUE 20: the seq-parallel long-prompt lane — one chunk dispatch
    # per tick; a per-chunk readback would serialize the whole prefill
    "_sp_prefill_step", "sp_prefill_chunk",
    "_span", "_record_tick",
    "record", "note", "poll",
    # ISSUE 16: the signal recorder samples inside _record_tick (the
    # tail of the hot section) — it must consume host floats only
    "sample", "evaluate_rules",
}

#: conventional device-resident value names in the hot path (plus any
#: name suffixed _dev): int()/float()/bool() over these is a readback
DEVICE_NAMES: Set[str] = {"logits", "final", "firsts", "block", "carry",
                          "toks3", "valid3"}

_LITERALS = (ast.List, ast.Tuple, ast.ListComp, ast.GeneratorExp,
             ast.Constant, ast.Dict, ast.Set, ast.SetComp, ast.DictComp)

#: annotation heads marking a parameter as a host-side container —
#: np.asarray over one is host->host operand assembly, not a device sync
_HOST_CONTAINER_ANNOTATIONS = {"list", "List", "tuple", "Tuple",
                               "Sequence", "Iterable", "dict", "Dict"}


def _host_container_params(fn: ast.FunctionDef):
    """Parameter names whose annotation is a host container type."""
    out = set()
    for arg in (list(fn.args.posonlyargs) + list(fn.args.args)
                + list(fn.args.kwonlyargs)):
        ann = arg.annotation
        if ann is None:
            continue
        head = ann.value if isinstance(ann, ast.Subscript) else ann
        if isinstance(head, ast.Name) and \
                head.id in _HOST_CONTAINER_ANNOTATIONS:
            out.add(arg.arg)
    return out


def _mentions_device_name(node: ast.AST) -> bool:
    for sub in ast.walk(node):
        if isinstance(sub, ast.Name):
            if sub.id in DEVICE_NAMES or sub.id.endswith("_dev"):
                return True
        if isinstance(sub, ast.Attribute):
            if sub.attr in DEVICE_NAMES or sub.attr.endswith("_dev"):
                return True
    return False


@register
class HostSyncRule(Rule):
    id = "BTF003"
    name = "host-sync-in-hot-path"
    invariant = ("tick/dispatch hot functions never materialize a "
                 "device value on the host (sync belongs to the "
                 "stacked drain)")
    scope = ("butterfly_tpu/engine/serving.py",
             "butterfly_tpu/sched/scheduler.py",
             "butterfly_tpu/obs/ticklog.py",
             "butterfly_tpu/obs/timeseries.py")

    def check(self, ctx: FileContext) -> Iterator[Finding]:
        for node in ast.walk(ctx.tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)) \
                    and node.name in HOT_FUNCTIONS:
                yield from self._check_hot(ctx, node)

    def _check_hot(self, ctx: FileContext,
                   fn: ast.FunctionDef) -> Iterator[Finding]:
        host_params = _host_container_params(fn)
        for node in ast.walk(fn):
            if not isinstance(node, ast.Call):
                continue
            name = call_name(node.func)
            where = f"in hot function {fn.name}()"
            if name in ("item", "tolist", "block_until_ready") and \
                    isinstance(node.func, ast.Attribute):
                yield self.finding(
                    ctx, node,
                    f".{name}() {where} synchronously materializes a "
                    f"device value — move it to the stacked drain")
                continue
            dotted = dotted_name(node.func)
            if dotted in ("jax.device_get",):
                yield self.finding(
                    ctx, node,
                    f"jax.device_get {where} blocks on the device — "
                    f"move it to the stacked drain")
                continue
            if dotted in ("np.asarray", "np.array", "numpy.asarray",
                          "numpy.array"):
                arg0 = node.args[0] if node.args else None
                is_host_param = (isinstance(arg0, ast.Name)
                                 and arg0.id in host_params)
                if arg0 is not None and not is_host_param and \
                        not isinstance(arg0, _LITERALS):
                    yield self.finding(
                        ctx, node,
                        f"{dotted}(...) on a non-literal {where} may "
                        f"fetch a device array to the host — convert at "
                        f"the drain, or build from host lists")
                continue
            if name in ("int", "float", "bool") and \
                    isinstance(node.func, ast.Name) and node.args and \
                    _mentions_device_name(node.args[0]):
                yield self.finding(
                    ctx, node,
                    f"{name}() over a device-carry value {where} is a "
                    f"per-token host readback (it serializes the "
                    f"device behind the host) — keep the value "
                    f"device-resident")

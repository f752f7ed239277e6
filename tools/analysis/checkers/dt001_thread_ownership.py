"""DT001 — thread-ownership race checker for the engine's two-thread design.

TpuEngine runs a dedicated scheduler thread (``_run``) that owns the
scheduler state: the run queues, the FIFO completion queue, slot free
lists, phase accounting. The asyncio side (``generate``/``embed``/…)
may only hand work across via the ``_wakeup`` condition's mutex, or ship
a closure to the scheduler thread with ``run_on_engine_thread``. PR 5's
scheduler-state mutations were only safe because a human remembered this;
DT001 makes the ownership machine-checked.

Declaration — either form, both honored:

- a class attribute ``_SCHED_OWNED = frozenset({"_fetchq", ...})``
- a trailing ``# owner: engine-thread`` comment on an ``self.x = ...``
  assignment in ``__init__``

Flagged:

- any read/write of an owned attribute lexically inside an ``async def``
  of the declaring class (or reachable from one through same-class sync
  method calls), unless the access sits under ``with self._mutex/_wakeup``
  (the documented cross-thread handoff protocol);
- accesses in OTHER modules' ``async def`` bodies through a receiver
  named like an engine (``engine``, ``_engine``, ``eng``, ``self.engine``)
  — the shape an async bench/test poking at scheduler internals takes.

Not flagged (by design, documented in docs/static-analysis.md): accesses
inside nested sync ``def``s (closures handed to ``run_on_engine_thread``
execute on the scheduler thread), and sync methods never called from an
async def in the same module (``metrics()``-style cross-thread readers
must take the mutex, but their call sites live in other processes'
handlers — the in-class rule is the load-bearing one).
"""

from __future__ import annotations

import ast
import re
from typing import Iterable

from tools.analysis.core import Checker, Finding, SourceModule, register, walk_function_body

OWNER_COMMENT_RE = re.compile(r"#\s*owner:\s*engine-thread\b")
LOCK_NAME_RE = re.compile(r"(mutex|lock|wakeup|cond)", re.IGNORECASE)
ENGINE_RECEIVERS = {"engine", "_engine", "eng", "self.engine", "self._engine"}


def _owned_names(cls: ast.ClassDef, module: SourceModule) -> frozenset[str]:
    names: set[str] = set()
    for node in cls.body:
        if isinstance(node, ast.Assign):
            targets = [t.id for t in node.targets if isinstance(t, ast.Name)]
            if "_SCHED_OWNED" in targets:
                for elt in ast.walk(node.value):
                    if isinstance(elt, ast.Constant) and isinstance(elt.value, str):
                        names.add(elt.value)
    # `self.x = ...  # owner: engine-thread` annotations anywhere in the class.
    for fn in cls.body:
        if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        for node in ast.walk(fn):
            if isinstance(node, (ast.Assign, ast.AnnAssign)):
                if OWNER_COMMENT_RE.search(module.line_text(node.lineno)):
                    tgts = node.targets if isinstance(node, ast.Assign) else [node.target]
                    for t in tgts:
                        if (
                            isinstance(t, ast.Attribute)
                            and isinstance(t.value, ast.Name)
                            and t.value.id == "self"
                        ):
                            names.add(t.attr)
    return frozenset(names)


def _under_lock(node: ast.AST, ancestors: dict[ast.AST, ast.AST]) -> bool:
    """True if any ancestor is `with self.<lock-ish>` (handoff protocol)."""
    cur = ancestors.get(node)
    while cur is not None:
        if isinstance(cur, ast.With):
            for item in cur.items:
                expr = item.context_expr
                if (
                    isinstance(expr, ast.Attribute)
                    and isinstance(expr.value, ast.Name)
                    and expr.value.id == "self"
                    and LOCK_NAME_RE.search(expr.attr)
                ):
                    return True
        cur = ancestors.get(cur)
    return False


def _ancestor_map(root: ast.AST) -> dict[ast.AST, ast.AST]:
    parents: dict[ast.AST, ast.AST] = {}
    for node in ast.walk(root):
        for child in ast.iter_child_nodes(node):
            parents[child] = node
    return parents


@register
class ThreadOwnershipChecker(Checker):
    code = "DT001"
    name = "thread-ownership"
    description = (
        "engine-scheduler-owned attributes touched from async code "
        "without the handoff mutex"
    )

    def run(self, module: SourceModule) -> Iterable[Finding]:
        assert module.tree is not None
        declares = False
        for cls in [n for n in ast.walk(module.tree) if isinstance(n, ast.ClassDef)]:
            owned = _owned_names(cls, module)
            if owned:
                declares = True
                yield from self._check_class(module, cls, owned)
        # Modules that declare a manifest are covered by the in-class pass;
        # everywhere else, catch async code reaching into an engine object.
        if not declares:
            yield from self._check_foreign_async(module)

    # -- in-class: async defs + sync methods they call ---------------------

    def _check_class(
        self, module: SourceModule, cls: ast.ClassDef, owned: frozenset[str]
    ) -> Iterable[Finding]:
        methods = {
            n.name: n
            for n in cls.body
            if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))
        }
        # Async-reachable set: async defs, plus same-class sync methods
        # transitively called from them (a sync helper invoked inline from
        # a coroutine still runs on the event loop thread).
        reachable: set[str] = set()
        frontier = [n for n, fn in methods.items() if isinstance(fn, ast.AsyncFunctionDef)]
        async_roots = set(frontier)
        while frontier:
            name = frontier.pop()
            if name in reachable:
                continue
            reachable.add(name)
            for node in walk_function_body(methods[name]):
                if (
                    isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)
                    and isinstance(node.func.value, ast.Name)
                    and node.func.value.id == "self"
                    and node.func.attr in methods
                ):
                    frontier.append(node.func.attr)

        for name in sorted(reachable):
            fn = methods[name]
            ancestors = _ancestor_map(fn)
            via = "" if name in async_roots else " (reached from an async def)"
            for node in walk_function_body(fn):
                if not (
                    isinstance(node, ast.Attribute)
                    and isinstance(node.value, ast.Name)
                    and node.value.id == "self"
                    and node.attr in owned
                ):
                    continue
                if _under_lock(node, ancestors):
                    continue
                yield Finding(
                    check=self.code, path=module.path, line=node.lineno,
                    message=(
                        f"engine-thread-owned attribute self.{node.attr} accessed "
                        f"in {cls.name}.{name}{via} outside the handoff mutex — "
                        "move onto the scheduler thread (run_on_engine_thread) "
                        "or guard with the engine condition lock"
                    ),
                    snippet=module.line_text(node.lineno),
                )

    # -- cross-module: async code poking engine internals ------------------

    def _check_foreign_async(self, module: SourceModule) -> Iterable[Finding]:
        # Names come from the engine manifest mirror below — the foreign
        # pass must not import jax to learn them, and receiver-name gating
        # (engine/_engine/eng) keeps the distinctive names precise.
        assert module.tree is not None
        for fn in ast.walk(module.tree):
            if not isinstance(fn, ast.AsyncFunctionDef):
                continue
            for node in walk_function_body(fn):
                if not (isinstance(node, ast.Attribute) and node.attr in _GLOBAL_OWNED):
                    continue
                recv = _receiver(node.value)
                if recv in ENGINE_RECEIVERS:
                    yield Finding(
                        check=self.code, path=module.path, line=node.lineno,
                        message=(
                            f"engine-thread-owned attribute {recv}.{node.attr} "
                            f"accessed from async def {fn.name} — use "
                            "run_on_engine_thread or an engine API"
                        ),
                        snippet=module.line_text(node.lineno),
                    )


def _receiver(node: ast.AST) -> str | None:
    if isinstance(node, ast.Name):
        return node.id
    if (
        isinstance(node, ast.Attribute)
        and isinstance(node.value, ast.Name)
        and node.value.id == "self"
    ):
        return f"self.{node.attr}"
    return None


# Mirror of TpuEngine._SCHED_OWNED (dynamo_tpu/engine/engine.py) for the
# cross-module pass, which must not import jax to learn it. test_analysis
# asserts the two sets stay equal.
_GLOBAL_OWNED = frozenset({
    "_submissions", "_waiting", "_running", "_fetchq", "_free_slots",
    "_embed_jobs", "_host_jobs", "_offload_pending", "_exports",
    "_export_fetches", "_drafter", "_step_no", "_spec_ticked",
    "phase_s", "phase_n", "_ctr_pushed", "_spec_depth_hist",
    "_migrations", "_anno", "_slots_blocked_sig",
    "phase_cpu_s", "_cur", "_cur_t", "_cur_cpu", "_t_run",
    "admission_stops", "prefill_waves", "wave_windows_ahead",
    "first_ready_unread_s", "first_fetches", "device_dry_s",
    "_dev_last", "_dev_open", "_dev_done", "_probe_t", "_probe_work",
})

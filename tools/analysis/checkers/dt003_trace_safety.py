"""DT003 — JAX trace-safety in jit/scan/shard_map-reachable code.

Inside traced code, a ``jax.Array`` is a tracer: ``float(x)`` /
``int(x)`` / ``bool(x)`` raise ``TracerConversionError`` (or worse,
silently bake a value at trace time), ``np.*`` on a tracer forces a
host transfer per call, and ``if tracer:`` either crashes or freezes one
branch into the compiled program. Donated buffers (``donate_argnums``)
are invalidated by the call — reading one afterwards returns garbage on
TPU even though it *works* on CPU, the nastiest class of "passes the
test suite, corrupts KV in prod".

Mechanics (pure AST, no jax import):

- Roots: functions decorated with / wrapped by ``jax.jit`` (incl. the
  module-level ``name = partial(jax.jit, ...)(impl)`` idiom), bodies
  passed to ``lax.scan`` / ``shard_map`` / ``jax.vmap`` /
  ``pl.pallas_call``.
- Reachability: same-module call graph from those roots (nested defs
  included — scan bodies are closures).
- Traced vs static params: ``static_argnums``/``static_argnames`` when
  given; otherwise parameter annotations — scalar Python types
  (int/float/bool/str) and config classes (``*Config``) are static,
  everything else (``jax.Array``, pytrees, unannotated) is traced.
  ``.shape``/``.dtype``/``.ndim``/``.size`` of a tracer are static
  metadata and never flagged.
- Donation: repo-wide. Call sites of donated jits are resolved through
  imports; a read of the donated argument after the call (before
  rebinding) is flagged.

Dataflow is intentionally shallow: direct parameter names only. A local
alias of a tracer escapes DT003 — the checker is a tripwire for the
common shapes, not an abstract interpreter (docs/static-analysis.md).
"""

from __future__ import annotations

import ast
from typing import Iterable

from tools.analysis.core import (
    Checker,
    Finding,
    SourceModule,
    dotted,
    register,
    walk_function_body,
)

STATIC_ANNOTATIONS = {"int", "float", "bool", "str", "bytes", "type", "Callable"}
TRACER_META_ATTRS = {"shape", "dtype", "ndim", "size", "sharding", "at"}
SCAN_LIKE = {
    "lax.scan", "jax.lax.scan", "shard_map", "jax.shard_map",
    "jax.vmap", "vmap", "pl.pallas_call", "pallas_call", "lax.fori_loop",
    "jax.lax.fori_loop", "lax.while_loop", "jax.lax.while_loop", "lax.cond",
    "jax.lax.cond", "jax.checkpoint", "jax.remat",
}
NP_ALIASES = {"np", "numpy", "onp"}


def _is_jit_wrapper(call: ast.Call) -> bool:
    """True for jax.jit(...) or (functools.)partial(jax.jit, ...)."""
    d = dotted(call.func)
    if d in {"jax.jit", "jit"}:
        return True
    if d in {"functools.partial", "partial"} and call.args:
        return dotted(call.args[0]) in {"jax.jit", "jit"}
    return False


def _int_tuple(node: ast.AST) -> tuple[int, ...]:
    vals: list[int] = []
    for n in ast.walk(node):
        if isinstance(n, ast.Constant) and isinstance(n.value, int):
            vals.append(n.value)
    return tuple(vals)


def _jit_meta(call: ast.Call) -> tuple[tuple[int, ...], tuple[str, ...], tuple[int, ...]]:
    """(static_argnums, static_argnames, donate_argnums) off a jit wrapper."""
    statics: tuple[int, ...] = ()
    names: tuple[str, ...] = ()
    donated: tuple[int, ...] = ()
    for kw in call.keywords:
        if kw.arg == "static_argnums":
            statics = _int_tuple(kw.value)
        elif kw.arg == "static_argnames":
            names = tuple(
                n.value for n in ast.walk(kw.value)
                if isinstance(n, ast.Constant) and isinstance(n.value, str)
            )
        elif kw.arg == "donate_argnums":
            donated = _int_tuple(kw.value)
    return statics, names, donated


FnDef = "ast.FunctionDef | ast.AsyncFunctionDef"


class _ModuleIndex:
    """Per-module: every function def (nested included) with its lexical
    scope chain, jit roots with their static info, and publicly-exported
    donated jits. Name resolution is scope-aware — ``q`` nested inside a
    jitted ``build`` must not collide with a module-level ``q``."""

    def __init__(self, module: SourceModule):
        self.module = module
        # function node -> chain of enclosing function nodes (innermost last)
        self.scope_of: dict[ast.AST, tuple[ast.AST, ...]] = {}
        # scope node (function or module) -> {name: def node} defined DIRECTLY in it
        self.defs_in: dict[ast.AST, dict[str, ast.AST]] = {}
        self.roots: list[ast.AST] = []
        # root node -> (static positions, static names)
        self.static_info: dict[ast.AST, tuple[tuple[int, ...], tuple[str, ...]]] = {}
        # exported name -> donated original arg positions
        self.donated: dict[str, tuple[int, ...]] = {}
        assert module.tree is not None
        self._collect_defs(module.tree)
        self._collect_roots(module.tree)

    def _collect_defs(self, tree: ast.Module) -> None:
        parents = _parent_map(tree)
        self.defs_in[tree] = {}
        for node in ast.walk(tree):
            if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            chain: list[ast.AST] = []
            cur = parents.get(node)
            while cur is not None:
                if isinstance(cur, (ast.FunctionDef, ast.AsyncFunctionDef)):
                    chain.append(cur)
                cur = parents.get(cur)
            chain.reverse()
            self.scope_of[node] = tuple(chain)
            owner = chain[-1] if chain else tree
            self.defs_in.setdefault(owner, {})[node.name] = node

    def resolve(self, name: str, env: tuple[ast.AST, ...]) -> ast.AST | None:
        """Resolve a bare function name from innermost scope outwards."""
        for scope in reversed(env):
            hit = self.defs_in.get(scope, {}).get(name)
            if hit is not None:
                return hit
        return None

    def _env_of(self, fn: ast.AST, module_tree: ast.AST) -> tuple[ast.AST, ...]:
        chain = self.scope_of.get(fn, ())
        return (module_tree,) + tuple(
            s for s in chain if isinstance(s, (ast.FunctionDef, ast.AsyncFunctionDef))
        ) + (fn,)

    def _collect_roots(self, tree: ast.Module) -> None:
        module_env = (tree,)
        # Decorated defs.
        for fn, chain in list(self.scope_of.items()):
            for dec in fn.decorator_list:  # type: ignore[attr-defined]
                if isinstance(dec, ast.Call) and _is_jit_wrapper(dec):
                    s, n, d = _jit_meta(dec)
                    self._add_root(fn, s, n)
                    if d:
                        self.donated[fn.name] = d  # type: ignore[attr-defined]
                elif dotted(dec) in {"jax.jit", "jit"}:
                    self._add_root(fn, (), ())
        # scan/shard_map/vmap bodies, resolved at the CALL SITE's scope.
        # walk_function_body prunes nested defs, so a call inside a nested
        # function is only seen when THAT function is the owner — a nested
        # scan body must never resolve against an outer scope's shadowed name.
        for owner, env in self._all_scopes(tree):
            for node in walk_function_body(owner):
                if isinstance(node, ast.Call) and dotted(node.func) in SCAN_LIKE and node.args:
                    body = dotted(node.args[0])
                    if body:
                        target = self.resolve(body.rsplit(".", 1)[-1], env)
                        if target is not None:
                            self._add_root(target, (), ())
        # Module-level `name = partial(jax.jit, ...)(impl)` / `jax.jit(impl)`.
        for node in ast.walk(tree):
            if not (isinstance(node, ast.Assign) and isinstance(node.value, ast.Call)):
                continue
            call = node.value
            inner: str | None = None
            meta: tuple | None = None
            if isinstance(call.func, ast.Call) and _is_jit_wrapper(call.func):
                if call.args:
                    inner = dotted(call.args[0])
                meta = _jit_meta(call.func)
            elif dotted(call.func) in {"jax.jit", "jit"} and call.args:
                inner = dotted(call.args[0])
                meta = _jit_meta(call)
            if inner is None or meta is None:
                continue
            target = self.resolve(inner.rsplit(".", 1)[-1], module_env)
            if target is None:
                continue
            statics, statnames, donated = meta
            self._add_root(target, statics, statnames)
            if donated:
                for t in node.targets:
                    tn = dotted(t)
                    if tn:
                        self.donated[tn.rsplit(".", 1)[-1]] = donated

    def _add_root(self, fn: ast.AST, statics, statnames) -> None:
        if fn not in self.static_info:
            self.roots.append(fn)
        self.static_info.setdefault(fn, (statics, statnames))

    def _all_scopes(self, tree: ast.Module):
        yield tree, (tree,)
        for fn in self.scope_of:
            yield fn, self._env_of(fn, tree)

    def reachable(self, tree: ast.Module) -> list[ast.AST]:
        seen: list[ast.AST] = []
        seen_ids: set[int] = set()
        frontier = list(self.roots)
        while frontier:
            fn = frontier.pop()
            if id(fn) in seen_ids:
                continue
            seen_ids.add(id(fn))
            seen.append(fn)
            env = self._env_of(fn, tree)
            for node in ast.walk(fn):
                if isinstance(node, ast.Call):
                    d = dotted(node.func)
                    if d:
                        target = self.resolve(d.rsplit(".", 1)[-1], env)
                        if target is not None and id(target) not in seen_ids:
                            frontier.append(target)
        return seen

    def traced_params(self, fn: ast.AST) -> set[str]:
        statics, statnames = self.static_info.get(fn, ((), ()))
        args = fn.args  # type: ignore[attr-defined]
        params = [a for a in args.posonlyargs + args.args]
        traced: set[str] = set()
        for i, arg in enumerate(params):
            if i in statics or arg.arg in statnames or arg.arg == "self":
                continue
            ann = arg.annotation
            if ann is not None:
                a = dotted(ann) or (
                    ann.value if isinstance(ann, ast.Constant) else None
                )
                if a in STATIC_ANNOTATIONS or (
                    isinstance(a, str) and a.rsplit(".", 1)[-1].endswith("Config")
                ):
                    continue
            traced.add(arg.arg)
        return traced


def _parent_map(root: ast.AST) -> dict[ast.AST, ast.AST]:
    parents: dict[ast.AST, ast.AST] = {}
    for node in ast.walk(root):
        for child in ast.iter_child_nodes(node):
            parents[child] = node
    return parents


def _traced_uses(expr: ast.AST, traced: set[str], parents: dict[ast.AST, ast.AST]) -> bool:
    """Does expr use a traced name *as a value* (not just its static
    .shape/.dtype metadata, len(), or isinstance())?"""
    for node in ast.walk(expr):
        if not (isinstance(node, ast.Name) and node.id in traced):
            continue
        parent = parents.get(node)
        if isinstance(parent, ast.Attribute) and parent.attr in TRACER_META_ATTRS:
            continue
        if isinstance(parent, ast.Call) and parent.args[:1] == [node]:
            f = dotted(parent.func)
            if f in {"len", "isinstance", "type", "id"}:
                continue
        # `x is None` / `x is not None` tests structure, not the traced
        # value — the canonical optional-argument branch is trace-safe.
        if isinstance(parent, ast.Compare) and all(
            isinstance(op, (ast.Is, ast.IsNot)) for op in parent.ops
        ):
            continue
        return True
    return False


@register
class TraceSafetyChecker(Checker):
    code = "DT003"
    name = "jax-trace-safety"
    description = (
        "tracer coercion / numpy-on-tracer / tracer branching / "
        "donated-buffer reuse in jit-reachable code"
    )
    scope = ("dynamo_tpu", "tools")

    def run_repo(self, modules) -> Iterable[Finding]:
        indexes: dict[str, _ModuleIndex] = {}
        donated_by_module: dict[str, dict[str, tuple[int, ...]]] = {}
        for m in modules:
            if m.tree is None or not self.applies(m):
                continue
            idx = _ModuleIndex(m)
            indexes[m.path] = idx
            if idx.donated:
                dotted_mod = m.path[:-3].replace("/", ".")
                donated_by_module[dotted_mod] = idx.donated
        for path, idx in indexes.items():
            # Dedupe: a nested scan body is both its own root and part of
            # its parent's walk; one finding per (line, message) is enough.
            seen: set[tuple[int, str]] = set()
            for f in self._check_traced_bodies(idx):
                if (f.line, f.message) not in seen:
                    seen.add((f.line, f.message))
                    yield f
        for m in modules:
            if m.tree is not None and self.applies(m):
                yield from self._check_donation(m, donated_by_module)
        # Donation applies to test code too: reading a donated cache after
        # handing it to prefill is wrong wherever it happens.
        for m in modules:
            if m.tree is not None and m.path.startswith("tests/"):
                yield from self._check_donation(m, donated_by_module)

    # -- traced-body rules --------------------------------------------------

    def _check_traced_bodies(self, idx: _ModuleIndex) -> Iterable[Finding]:
        module = idx.module
        assert module.tree is not None
        for fn in idx.reachable(module.tree):
            name = getattr(fn, "name", "<fn>")
            traced = idx.traced_params(fn)
            if not traced:
                continue
            parents = _parent_map(fn)
            for node in ast.walk(fn):
                if isinstance(node, ast.Call):
                    d = dotted(node.func)
                    if (
                        d in {"float", "int", "bool", "complex"}
                        and node.args
                        and _traced_uses(node.args[0], traced, parents)
                    ):
                        yield self._finding(
                            module, node.lineno,
                            f"in jit-reachable {name}: {d}() on traced value "
                            "concretizes a tracer — use jnp/astype or hoist "
                            "out of the traced region",
                        )
                    elif d and d.split(".", 1)[0] in NP_ALIASES and any(
                        _traced_uses(a, traced, parents)
                        for a in list(node.args) + [kw.value for kw in node.keywords]
                    ):
                        yield self._finding(
                            module, node.lineno,
                            f"in jit-reachable {name}: numpy call {d}(...) on a "
                            "traced value forces a host round-trip per step — "
                            "use jnp",
                        )
                elif isinstance(node, (ast.If, ast.While)):
                    if _traced_uses(node.test, traced, parents):
                        yield self._finding(
                            module, node.lineno,
                            f"in jit-reachable {name}: Python branch on a traced "
                            "value — truthiness concretizes the tracer; use "
                            "jnp.where / lax.cond",
                        )
                elif isinstance(node, ast.Assert) and _traced_uses(
                    node.test, traced, parents
                ):
                    yield self._finding(
                        module, node.lineno,
                        f"in jit-reachable {name}: assert on a traced value — "
                        "use checkify or assert on static metadata",
                    )

    # -- donated-buffer reuse ----------------------------------------------

    def _check_donation(
        self, module: SourceModule, donated_by_module: dict[str, dict[str, tuple[int, ...]]]
    ) -> Iterable[Finding]:
        assert module.tree is not None
        # alias -> defining module dotted path (import model as M / from x import prefill)
        alias_mod: dict[str, str] = {}
        direct: dict[str, tuple[str, tuple[int, ...]]] = {}  # local name -> (qual, donated)
        for node in ast.walk(module.tree):
            if isinstance(node, ast.Import):
                for a in node.names:
                    if a.name in donated_by_module:
                        alias_mod[a.asname or a.name.split(".")[-1]] = a.name
            elif isinstance(node, ast.ImportFrom) and node.module:
                if node.module in donated_by_module:
                    dmap = donated_by_module[node.module]
                    for a in node.names:
                        if a.name in dmap:
                            direct[a.asname or a.name] = (
                                f"{node.module}.{a.name}", dmap[a.name]
                            )
                # `from dynamo_tpu.engine import model as M`
                for a in node.names:
                    cand = f"{node.module}.{a.name}"
                    if cand in donated_by_module:
                        alias_mod[a.asname or a.name] = cand
        if not alias_mod and not direct:
            return
        for fn in ast.walk(module.tree):
            if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            yield from self._check_donation_in_fn(module, fn, alias_mod, direct, donated_by_module)

    def _check_donation_in_fn(
        self, module, fn, alias_mod, direct, donated_by_module
    ) -> Iterable[Finding]:
        # Stay within THIS function's scope: nested defs are analyzed as
        # their own functions (a closure's donation is its own business).
        calls: list[tuple[ast.Call, str, tuple[int, ...]]] = []
        for node in walk_function_body(fn):
            if not isinstance(node, ast.Call):
                continue
            if isinstance(node.func, ast.Name) and node.func.id in direct:
                qual, dpos = direct[node.func.id]
                calls.append((node, qual, dpos))
            elif isinstance(node.func, ast.Attribute):
                base = dotted(node.func.value)
                if base in alias_mod:
                    dmap = donated_by_module[alias_mod[base]]
                    if node.func.attr in dmap:
                        calls.append((
                            node, f"{alias_mod[base]}.{node.func.attr}",
                            dmap[node.func.attr],
                        ))
        if not calls:
            return
        # Linear-order use-after-donate: a Load of the donated name on a
        # later line than the call, before any later-line rebind.
        loads: dict[str, list[int]] = {}
        stores: dict[str, list[int]] = {}
        for node in walk_function_body(fn):
            d = dotted(node) if isinstance(node, (ast.Name, ast.Attribute)) else None
            if d is None:
                continue
            ctx = getattr(node, "ctx", None)
            if isinstance(ctx, ast.Store):
                stores.setdefault(d, []).append(node.lineno)
            elif isinstance(ctx, ast.Load):
                loads.setdefault(d, []).append(node.lineno)
        for call, qual, dpos in calls:
            call_end = getattr(call, "end_lineno", call.lineno) or call.lineno
            for pos in dpos:
                if pos >= len(call.args):
                    continue
                name = dotted(call.args[pos])
                if name is None:
                    continue
                rebinds = [ln for ln in stores.get(name, []) if ln >= call.lineno]
                next_rebind = min(rebinds) if rebinds else 1 << 30
                bad = [
                    ln for ln in loads.get(name, [])
                    if call_end < ln <= next_rebind
                ]
                # A rebind on the same line as a load (x = f(x)) is fine.
                bad = [ln for ln in bad if ln not in stores.get(name, [])]
                if bad:
                    yield self._finding(
                        module, bad[0],
                        f"{name} was donated to {qual} on line {call.lineno} "
                        "(donate_argnums) — its buffer is invalid after the "
                        "call; rebind the result or copy first",
                    )

    def _finding(self, module: SourceModule, line: int, message: str) -> Finding:
        return Finding(
            check=self.code, path=module.path, line=line,
            message=message, snippet=module.line_text(line),
        )

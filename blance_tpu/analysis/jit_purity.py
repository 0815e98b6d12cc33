"""jit-purity lint: contracts for code that runs under a JAX trace.

Everything inside a ``jax.jit`` / ``shard_map`` trace must be pure and
shape-deterministic, or the warm-replan identity contract (plan/tensor.py:
bit-identical warm vs cold solves, pinned tie-break bits) silently breaks:
host nondeterminism bakes a one-off value into the compiled program,
Python branching on traced values either crashes at trace time or forks
the cache, and host coercions force device syncs mid-dispatch.

The pass builds a cross-module call graph rooted at every function handed
to ``jax.jit`` (decorator, ``jax.jit(f)``, ``partial(jax.jit, ...)``) or
to a ``shard_map``-shaped wrapper (including through ``partial`` aliases,
the idiom parallel/sharded.py uses), then walks the reachable set:

- JIT001 (all reached code): host nondeterminism — ``time.*``,
  ``random.*`` / ``numpy.random.*``, ``datetime.now``, ``os.urandom``,
  ``uuid.*``.  A traced call bakes ONE sample into the compiled program;
  every later call replays it.
- JIT002 (trace roots, where static args are declared): Python ``if`` /
  ``while`` branching directly on a traced parameter.  ``is None`` /
  ``is not None`` tests are exempt (argument *presence* is static).
- JIT003 (trace roots): ``float()`` / ``int()`` / ``bool()`` applied to a
  traced parameter — a forced device sync (and a trace-time error under
  jit).
- JIT004 (all reached code): mutation of captured state — ``global`` /
  ``nonlocal`` declarations, or mutating method calls
  (append/extend/update/...) on names not bound in the local scope.
  Traced mutations of captured Python state run ONCE, at trace time.
- JIT005 (jit call sites): static-arg hygiene — ``static_argnames`` /
  ``donate_argnames`` naming a parameter the wrapped function does not
  have (jit raises only when the name is actually passed), static
  parameters whose declared default is an unhashable literal, and
  ``donate_argnums`` indices that fall outside the wrapped function's
  positional parameters or land on a declared static (jax rejects both
  only at dispatch time, so the misdeclaration hides until a call site
  exercises it).

Helpers reached from a root get JIT001/JIT004 only: without the root's
``static_argnames`` there is no ground truth for which helper parameters
are traced, and guessing would drown the signal in false positives (the
analysis/baseline.toml workflow exists for the cases the pass cannot
prove).
"""

from __future__ import annotations

import ast
from typing import Optional

from . import Finding
from ._astutil import FuncInfo, ModuleIndex, ModuleInfo
from ._astutil import dotted as _dotted

__all__ = ["JitPurityPass"]

# fq-prefix -> why it is impure under a trace.
_NONDET_PREFIXES = {
    "time.time": "wall-clock read",
    "time.time_ns": "wall-clock read",
    "time.perf_counter": "wall-clock read",
    "time.monotonic": "wall-clock read",
    "time.sleep": "host sleep",
    "random.": "host PRNG (use jax.random with an explicit key)",
    "numpy.random.": "host PRNG (use jax.random with an explicit key)",
    "datetime.datetime.now": "wall-clock read",
    "datetime.date.today": "wall-clock read",
    "os.urandom": "host entropy",
    "uuid.": "host entropy",
}

_MUTATING_METHODS = {
    "append", "extend", "insert", "remove", "pop", "clear", "update",
    "setdefault", "add", "discard", "popitem",
}

_COERCIONS = {"float", "int", "bool"}

# Callables that wrap a function for tracing, matched by the leaf of
# the resolved dotted name (``jax.shard_map``).
_TRACE_WRAPPER_SUFFIXES = ("shard_map",)


def _literal_strings(node: ast.AST, constants: dict[str, object]
                     ) -> Optional[list[str]]:
    """Extract a tuple/list of string literals, following one level of
    module-constant indirection (the ``_WARM_STATICS`` idiom)."""
    if isinstance(node, ast.Name) and node.id in constants:
        val = constants[node.id]
        if isinstance(val, (tuple, list)) and \
                all(isinstance(x, str) for x in val):
            return list(val)
        return None
    if isinstance(node, (ast.Tuple, ast.List)):
        out = []
        for elt in node.elts:
            if isinstance(elt, ast.Constant) and isinstance(elt.value, str):
                out.append(elt.value)
            else:
                return None
        return out
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return [node.value]
    return None


def _literal_ints(node: ast.AST, constants: dict[str, object]
                  ) -> Optional[list[int]]:
    """Extract a tuple/list of int literals (or a single int), following
    one level of module-constant indirection — the ``donate_argnums``
    twin of :func:`_literal_strings`."""
    if isinstance(node, ast.Name) and node.id in constants:
        val = constants[node.id]
        if isinstance(val, int) and not isinstance(val, bool):
            return [val]
        if isinstance(val, (tuple, list)) and \
                all(isinstance(x, int) and not isinstance(x, bool)
                    for x in val):
            return [int(x) for x in val]
        return None
    if isinstance(node, (ast.Tuple, ast.List)):
        out: list[int] = []
        for elt in node.elts:
            if isinstance(elt, ast.Constant) and \
                    isinstance(elt.value, int) and \
                    not isinstance(elt.value, bool):
                out.append(elt.value)
            else:
                return None
        return out
    if isinstance(node, ast.Constant) and isinstance(node.value, int) and \
            not isinstance(node.value, bool):
        return [node.value]
    return None


class JitPurityPass:
    """Whole-program pass: build the index, find roots, walk, lint."""

    def __init__(self, files: list[str], repo_root: str) -> None:
        self.repo_root = repo_root
        self.index = ModuleIndex(files, repo_root)
        self.modules: dict[str, ModuleInfo] = self.index.modules
        self.findings: list[Finding] = []
        for rel, line, msg in self.index.parse_errors:
            self.findings.append(Finding(
                rule="JIT000", path=rel, line=line, symbol="",
                message=f"file does not parse: {msg}"))

    # -- root discovery -----------------------------------------------------

    def _is_jit_ref(self, mi: ModuleInfo, node: ast.AST) -> bool:
        dotted = _dotted(node)
        if dotted is None:
            return False
        fq = self.index.resolve(mi, dotted)
        return fq in ("jax.jit", "jax.pjit", "jax.jit.jit") or \
            fq.endswith(".jit") and fq.startswith("jax")

    def _is_trace_wrapper_ref(self, mi: ModuleInfo, node: ast.AST) -> bool:
        dotted = _dotted(node)
        if dotted is None:
            return False
        fq = self.index.resolve(mi, dotted)
        return fq.split(".")[-1] in _TRACE_WRAPPER_SUFFIXES

    def _mark_root(self, mi: ModuleInfo, func_ref: ast.AST,
                   statics: set[str], aliases: dict[str, str]) -> None:
        """func_ref names (possibly via a partial alias) a function."""
        target = None
        if isinstance(func_ref, ast.Call):
            # partial(f, ...) inline
            inner = self.index.partial_target(mi, func_ref)
            if inner is not None:
                target = inner
        else:
            dotted = _dotted(func_ref)
            if dotted is not None:
                if dotted in aliases:
                    dotted = aliases[dotted]
                target = self.index.lookup_function(mi, dotted)
        if target is not None:
            target.is_root = True
            target.statics |= statics

    def _jit_statics(self, mi: ModuleInfo, call: ast.Call,
                     wrapped) -> set[str]:
        """Parse static_argnames/donate_argnames off a jit(...) call,
        emitting JIT005 findings against the wrapped function.  Only
        static argnames are returned (donated args are still traced)."""
        statics: set[str] = set()
        for kw in call.keywords:
            if kw.arg not in ("static_argnames", "donate_argnames"):
                continue
            names = _literal_strings(kw.value, mi.constants)
            if names is None:
                continue
            if kw.arg == "static_argnames":
                statics |= set(names)
            if wrapped is not None:
                missing = [n for n in names if n not in wrapped.params]
                for n in missing:
                    self.findings.append(Finding(
                        rule="JIT005", path=mi.path, line=call.lineno,
                        symbol=wrapped.qualname,
                        message=f"{kw.arg} names {n!r} which is not a "
                                f"parameter of {wrapped.qualname}() — jit "
                                f"only raises when the name is passed, so "
                                f"this typo hides until a call site uses "
                                f"it"))
        self._jit_argnums(mi, call, wrapped, statics)
        return statics

    def _jit_argnums(self, mi: ModuleInfo, call: ast.Call, wrapped,
                     statics: set[str]) -> None:
        """donate_argnums hygiene: positional indices are resolved by jax
        only at dispatch time, so an out-of-range index or one landing on
        a declared static parameter (jax refuses to donate statics)
        hides until a call site exercises the donating path."""
        if wrapped is None:
            return
        args = wrapped.node.args
        pos = [a.arg for a in args.posonlyargs] + [a.arg for a in args.args]
        for kw in call.keywords:
            if kw.arg != "donate_argnums":
                continue
            nums = _literal_ints(kw.value, mi.constants)
            if nums is None:
                continue
            for i in nums:
                if i < 0 or i >= len(pos):
                    self.findings.append(Finding(
                        rule="JIT005", path=mi.path, line=call.lineno,
                        symbol=wrapped.qualname,
                        message=f"donate_argnums index {i} is outside "
                                f"{wrapped.qualname}()'s "
                                f"{len(pos)} positional parameter(s) — "
                                f"jit only raises at dispatch time, so "
                                f"the bad index hides until the donating "
                                f"path runs"))
                elif pos[i] in statics:
                    self.findings.append(Finding(
                        rule="JIT005", path=mi.path, line=call.lineno,
                        symbol=wrapped.qualname,
                        message=f"donate_argnums index {i} names "
                                f"{pos[i]!r} which is also declared in "
                                f"static_argnames — a static argument "
                                f"has no device buffer to donate, and "
                                f"jax rejects the overlap only at "
                                f"dispatch time"))

    def _find_roots(self) -> None:
        for mi in self.modules.values():
            # partial aliases: var = partial(f, ...) / var = f, per module
            # (function-local aliases are collected per function below).
            aliases = self._collect_aliases(mi, mi.tree)
            # 1) decorators
            for fn in mi.functions.values():
                for dec in fn.node.decorator_list:
                    self._root_from_decorator(mi, fn, dec)
            # 2) any jit(...) / shard_map-ish call anywhere
            for node in ast.walk(mi.tree):
                if not isinstance(node, ast.Call):
                    continue
                if self._is_jit_ref(mi, node.func):
                    wrapped = None
                    if node.args:
                        dotted = _dotted(node.args[0])
                        if dotted is not None:
                            dotted = aliases.get(dotted, dotted)
                            wrapped = self.index.lookup_function(mi, dotted)
                    statics = self._jit_statics(mi, node, wrapped)
                    if wrapped is not None:
                        wrapped.is_root = True
                        wrapped.statics |= statics
                elif isinstance(node.func, ast.Call):
                    # partial(jax.jit, static_argnames=...)(f)
                    inner = node.func
                    if isinstance(inner, ast.Call) and inner.args and \
                            self._is_jit_ref(mi, inner.args[0]) and \
                            self.index.resolve(
                                mi, _dotted(inner.func) or "") == \
                            "functools.partial":
                        wrapped = None
                        if node.args:
                            dotted = _dotted(node.args[0])
                            if dotted is not None:
                                dotted = aliases.get(dotted, dotted)
                                wrapped = self.index.lookup_function(mi, dotted)
                        statics = self._jit_statics(mi, inner, wrapped)
                        if wrapped is not None:
                            wrapped.is_root = True
                            wrapped.statics |= statics
                if self._is_trace_wrapper_ref(mi, node.func):
                    for arg in list(node.args) + \
                            [kw.value for kw in node.keywords]:
                        self._mark_root(mi, arg, set(), aliases)
                # partial(jax.shard_map, body, ...): a wrapper call too
                dotted = _dotted(node.func)
                if dotted is not None and \
                        self.index.resolve(mi, dotted) == "functools.partial" \
                        and node.args and \
                        self._is_trace_wrapper_ref(mi, node.args[0]):
                    for arg in list(node.args[1:]) + \
                            [kw.value for kw in node.keywords]:
                        self._mark_root(mi, arg, set(), aliases)

    def _root_from_decorator(self, mi: ModuleInfo, fn: FuncInfo,
                             dec: ast.AST) -> None:
        if self._is_jit_ref(mi, dec):  # @jax.jit
            fn.is_root = True
            return
        if isinstance(dec, ast.Call):
            if self._is_jit_ref(mi, dec.func):  # @jax.jit(...)
                fn.is_root = True
                fn.statics |= self._jit_statics(mi, dec, fn)
            elif dec.args and self._is_jit_ref(mi, dec.args[0]) and \
                    self.index.resolve(mi, _dotted(dec.func) or "") == \
                    "functools.partial":  # @partial(jax.jit, ...)
                fn.is_root = True
                fn.statics |= self._jit_statics(mi, dec, fn)

    def _collect_aliases(self, mi: ModuleInfo,
                         tree: ast.AST) -> dict[str, str]:
        """name -> dotted function reference, for ``x = partial(f, ...)``
        and ``x = f`` bindings."""
        aliases: dict[str, str] = {}
        for node in ast.walk(tree):
            if not isinstance(node, ast.Assign) or len(node.targets) != 1:
                continue
            tgt = node.targets[0]
            if not isinstance(tgt, ast.Name):
                continue
            val = node.value
            if isinstance(val, ast.Call):
                info = self.index.partial_target(mi, val)
                if info is not None and info.module == mi.name:
                    aliases[tgt.id] = info.qualname
                elif info is not None:
                    aliases[tgt.id] = info.fq
            else:
                dotted = _dotted(val)
                if dotted is not None and \
                        self.index.lookup_function(mi, dotted) is not None:
                    aliases[tgt.id] = dotted
        return aliases

    # -- reachability -------------------------------------------------------

    def _reachable(self) -> list[FuncInfo]:
        roots = [fn for mi in self.modules.values()
                 for fn in mi.functions.values() if fn.is_root]
        return self.index.reachable(roots)

    # -- the lint -----------------------------------------------------------

    def run(self) -> list[Finding]:
        self._find_roots()
        for fn in self._reachable():
            self._lint_function(fn)
        return self.findings

    def _emit(self, fn: FuncInfo, rule: str, line: int,
              message: str) -> None:
        self.findings.append(Finding(
            rule=rule, path=fn.path, line=line, symbol=fn.qualname,
            message=message))

    def _local_names(self, fn: FuncInfo) -> set[str]:
        names = set(fn.params)
        for node in ast.walk(fn.node):
            if isinstance(node, (ast.Assign, ast.AnnAssign, ast.AugAssign)):
                targets = node.targets if isinstance(node, ast.Assign) \
                    else [node.target]
                for t in targets:
                    for sub in ast.walk(t):
                        # Only true bindings: in ``x[k] = v`` / ``x.a = v``
                        # the base name is a Load — x stays captured.
                        if isinstance(sub, ast.Name) and \
                                isinstance(sub.ctx, ast.Store):
                            names.add(sub.id)
            elif isinstance(node, (ast.For, ast.AsyncFor)):
                for sub in ast.walk(node.target):
                    if isinstance(sub, ast.Name):
                        names.add(sub.id)
            elif isinstance(node, ast.comprehension):
                for sub in ast.walk(node.target):
                    if isinstance(sub, ast.Name):
                        names.add(sub.id)
            elif isinstance(node, (ast.With, ast.AsyncWith)):
                for item in node.items:
                    if item.optional_vars is not None:
                        for sub in ast.walk(item.optional_vars):
                            if isinstance(sub, ast.Name):
                                names.add(sub.id)
        return names

    def _lint_function(self, fn: FuncInfo) -> None:
        mi = self.modules[fn.module]
        local = self._local_names(fn)
        traced = set(fn.params) - fn.statics - fn.defaulted - \
            {"self", "cls"}

        for node in ast.walk(fn.node):
            # JIT004: captured-state mutation
            if isinstance(node, (ast.Global, ast.Nonlocal)):
                self._emit(fn, "JIT004", node.lineno,
                           f"{'global' if isinstance(node, ast.Global) else 'nonlocal'} "
                           f"mutation inside traced code runs once, at "
                           f"trace time — not per call")
                continue
            if not isinstance(node, ast.Call):
                continue

            dotted = _dotted(node.func)
            if dotted is not None:
                fq = self.index.resolve(mi, dotted)
                # JIT001: host nondeterminism
                for prefix, why in _NONDET_PREFIXES.items():
                    hit = fq == prefix or (prefix.endswith(".") and
                                           fq.startswith(prefix))
                    if hit:
                        self._emit(
                            fn, "JIT001", node.lineno,
                            f"call to {fq} under a jit trace: {why}; the "
                            f"traced value is baked into the compiled "
                            f"program and replayed on every call")
                        break

            # JIT004: mutating a captured name
            if isinstance(node.func, ast.Attribute) and \
                    node.func.attr in _MUTATING_METHODS and \
                    isinstance(node.func.value, ast.Name) and \
                    node.func.value.id not in local:
                self._emit(
                    fn, "JIT004", node.lineno,
                    f"mutating call {node.func.value.id}."
                    f"{node.func.attr}() targets captured state — under "
                    f"a trace this runs once, at trace time")

            # JIT003: device-sync coercion of a traced param (roots only)
            if fn.is_root and isinstance(node.func, ast.Name) and \
                    node.func.id in _COERCIONS and len(node.args) == 1:
                arg = node.args[0]
                names = {n.id for n in ast.walk(arg)
                         if isinstance(n, ast.Name)}
                # int(x.shape[0]) coerces a STATIC fact about x, not x.
                under_attr = {
                    n.id
                    for sub in ast.walk(arg)
                    if isinstance(sub, ast.Attribute)
                    for n in ast.walk(sub.value)
                    if isinstance(n, ast.Name)
                }
                hits = (names - under_attr) & traced
                if hits:
                    self._emit(
                        fn, "JIT003", node.lineno,
                        f"{node.func.id}() on traced value "
                        f"{sorted(hits)[0]!r}: a forced device sync "
                        f"(TracerConversionError under jit)")

        # JIT002: branching on traced values (roots only)
        if fn.is_root:
            for node in ast.walk(fn.node):
                if not isinstance(node, (ast.If, ast.While)):
                    continue
                hits = self._traced_branch_names(node.test, traced)
                if hits:
                    self._emit(
                        fn, "JIT002", node.lineno,
                        f"Python {'if' if isinstance(node, ast.If) else 'while'} "
                        f"on traced parameter {sorted(hits)[0]!r}: trace-"
                        f"time branching forks the compile cache or "
                        f"raises TracerBoolConversionError; use lax.cond/"
                        f"jnp.where, or declare it static")

    def _traced_branch_names(self, test: ast.AST,
                             traced: set[str]) -> set[str]:
        """Direct traced-parameter references in a branch test, minus
        ``x is None`` / ``x is not None`` presence checks and attribute
        accesses (``x.shape`` etc. are static under tracing)."""
        exempt: set[str] = set()
        for node in ast.walk(test):
            if isinstance(node, ast.Compare) and \
                    all(isinstance(op, (ast.Is, ast.IsNot))
                        for op in node.ops):
                for sub in [node.left] + node.comparators:
                    if isinstance(sub, ast.Name):
                        exempt.add(sub.id)
            elif isinstance(node, ast.Attribute):
                # x.shape / x.ndim / x.dtype: static facts about x
                for sub in ast.walk(node.value):
                    if isinstance(sub, ast.Name):
                        exempt.add(sub.id)
            elif isinstance(node, ast.Call):
                fnode = node.func
                if isinstance(fnode, ast.Name) and \
                        fnode.id in ("isinstance", "len", "hasattr"):
                    for a in node.args:
                        for sub in ast.walk(a):
                            if isinstance(sub, ast.Name):
                                exempt.add(sub.id)
        names = {n.id for n in ast.walk(test)
                 if isinstance(n, ast.Name) and isinstance(n.ctx, ast.Load)}
        return (names & traced) - exempt

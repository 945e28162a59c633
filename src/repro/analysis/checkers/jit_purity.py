"""jit-purity: no host side effects inside traced functions.

``jax.jit`` traces a function once and replays the compiled
computation; host side effects inside the traced body execute at trace
time only (or never again), so a ``time.time()``, an unseeded
``random``/``np.random`` draw, ``print``, file I/O, a profiler span
(``jax.profiler.TraceAnnotation``), or ``global``/``nonlocal`` mutation
there is almost always a bug — the value is
frozen into the compiled graph and every later call silently reuses
it.  This rule finds every function that flows into ``jax.jit`` /
``jax.vmap`` / ``jax.pmap`` / ``jax.lax.scan`` (decorators, including
``functools.partial(jax.jit, ...)``; direct calls; lambdas) and flags
host-effect calls in its body, walking one call level deep into
same-module helpers.

Seeded constructors are allowed: ``np.random.default_rng(seed)`` /
``random.Random(seed)`` with an argument are deterministic factories,
not hidden global-state draws.
"""
from __future__ import annotations

import ast
from typing import Dict, Iterable, List, Optional, Set, Tuple

from ..core import Checker, ModuleInfo, Violation, register

# dotted-call suffixes that are host effects inside a traced function
_EFFECT_CALLS = {
    "time.time": "reads the host clock at trace time",
    "time.perf_counter": "reads the host clock at trace time",
    "time.monotonic": "reads the host clock at trace time",
    "time.sleep": "blocks the host at trace time only",
    "datetime.now": "reads the host clock at trace time",
    "os.urandom": "draws host entropy at trace time",
    # also matches jax.profiler.TraceAnnotation and other dotted spellings
    "TraceAnnotation": "opens a profiler span at trace time only, never "
                       "when the program runs",
    "StepTraceAnnotation": "opens a profiler span at trace time only, "
                           "never when the program runs",
}
# bare names that are host effects
_EFFECT_NAMES = {
    "print": "prints at trace time only, then never again",
    "open": "performs file I/O at trace time",
    "input": "blocks on host input at trace time",
}
# random-module draw functions (unseeded global state)
_RANDOM_DRAWS = {
    "random", "randint", "randrange", "uniform", "gauss", "normal",
    "choice", "shuffle", "sample", "rand", "randn", "random_sample",
    "permutation",
}
_JIT_ENTRY_SUFFIXES = ("jit", "vmap", "pmap")
_SCAN_SUFFIXES = ("scan", "fori_loop", "while_loop", "cond", "map")


def _dotted(node: ast.expr) -> str:
    """Best-effort dotted name for a call target ('jax.lax.scan')."""
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
    return ".".join(reversed(parts))


def _is_jit_entry(call_target: ast.expr) -> bool:
    name = _dotted(call_target)
    if not name:
        return False
    last = name.split(".")[-1]
    if last in _JIT_ENTRY_SUFFIXES:
        return True
    # jax.lax.scan / lax.scan / lax.fori_loop etc.
    if last in _SCAN_SUFFIXES and ("lax" in name.split(".")
                                   or name.startswith("jax.")):
        return True
    return False


def _partial_jit(call: ast.Call) -> bool:
    """functools.partial(jax.jit, static_argnames=...) used as decorator."""
    if _dotted(call.func).split(".")[-1] != "partial":
        return False
    return bool(call.args) and _is_jit_entry(call.args[0])


@register
class JitPurityChecker(Checker):
    rule = "jit-purity"
    description = ("no host side effects (clock, unseeded random, I/O, "
                   "print, global mutation) reachable inside jitted "
                   "functions, one call level deep")

    def check_module(self, mod: ModuleInfo) -> Iterable[Violation]:
        defs: Dict[str, ast.AST] = {}
        for node in ast.walk(mod.tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                defs.setdefault(node.name, node)

        roots: List[Tuple[ast.AST, str]] = []  # (func node, how traced)
        seen: Set[int] = set()

        def add_root(fn: Optional[ast.AST], how: str) -> None:
            if fn is not None and id(fn) not in seen:
                seen.add(id(fn))
                roots.append((fn, how))

        for node in ast.walk(mod.tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                for dec in node.decorator_list:
                    target = dec.func if isinstance(dec, ast.Call) else dec
                    if _is_jit_entry(target) or (
                            isinstance(dec, ast.Call) and _partial_jit(dec)):
                        add_root(node, _dotted(target) or "jit")
            if isinstance(node, ast.Call) and _is_jit_entry(node.func):
                how = _dotted(node.func)
                for arg in node.args:
                    if isinstance(arg, ast.Lambda):
                        add_root(arg, how)
                    elif isinstance(arg, ast.Name) and arg.id in defs:
                        add_root(defs[arg.id], how)
                    elif isinstance(arg, ast.Attribute) \
                            and isinstance(arg.value, ast.Name) \
                            and arg.value.id == "self" \
                            and "_" + arg.attr in defs:
                        pass  # method refs resolved below by bare name
                # self._method / cls._method references
                for arg in node.args:
                    if isinstance(arg, ast.Attribute) \
                            and arg.attr in defs:
                        add_root(defs[arg.attr], how)

        out: List[Violation] = []
        for fn, how in roots:
            out.extend(self._check_body(mod, fn, how, defs, depth=0))
        return out

    def _check_body(self, mod: ModuleInfo, fn: ast.AST, how: str,
                    defs: Dict[str, ast.AST], depth: int,
                    _visited: Optional[Set[int]] = None
                    ) -> Iterable[Violation]:
        visited = _visited if _visited is not None else set()
        if id(fn) in visited:
            return []
        visited.add(id(fn))
        out: List[Violation] = []
        body = fn.body if isinstance(fn.body, list) else [fn.body]
        label = getattr(fn, "name", "<lambda>")

        for node in ast.walk(ast.Module(body=body, type_ignores=[])):
            if isinstance(node, (ast.Global, ast.Nonlocal)):
                out.append(self.violation(
                    mod, node,
                    f"{label} (traced via {how}) mutates "
                    f"{'global' if isinstance(node, ast.Global) else 'nonlocal'}"
                    f" state {', '.join(node.names)} — the mutation runs at "
                    f"trace time only", symbol=label))
            if not isinstance(node, ast.Call):
                continue
            name = _dotted(node.func)
            last = name.split(".")[-1] if name else ""
            if name in _EFFECT_NAMES and isinstance(node.func, ast.Name):
                out.append(self.violation(
                    mod, node,
                    f"{label} (traced via {how}) calls {name}() which "
                    f"{_EFFECT_NAMES[name]}", symbol=label))
                continue
            for suffix, why in _EFFECT_CALLS.items():
                if name == suffix or name.endswith("." + suffix):
                    out.append(self.violation(
                        mod, node,
                        f"{label} (traced via {how}) calls {name}() which "
                        f"{why}", symbol=label))
                    break
            else:
                if last in _RANDOM_DRAWS and name and (
                        name.startswith("random.")
                        or ".random." in name
                        or name.startswith("np.random")
                        or name.startswith("numpy.random")):
                    out.append(self.violation(
                        mod, node,
                        f"{label} (traced via {how}) draws from unseeded "
                        f"global randomness {name}() — use jax.random with "
                        f"an explicit key", symbol=label))
                elif last in ("Random", "default_rng", "seed") \
                        and not node.args and not node.keywords \
                        and ("random" in name):
                    out.append(self.violation(
                        mod, node,
                        f"{label} (traced via {how}) constructs {name}() "
                        f"without a seed — trace-time entropy makes the "
                        f"compiled function nondeterministic",
                        symbol=label))
                elif depth == 0 and isinstance(node.func, ast.Name) \
                        and node.func.id in defs:
                    # walk one call level deep into same-module helpers
                    out.extend(self._check_body(
                        mod, defs[node.func.id], f"{how} via {label}",
                        defs, depth=1, _visited=visited))
        return out

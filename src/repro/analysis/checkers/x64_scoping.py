"""x64-scoping: float64 in kernels/ only under scoped ``enable_x64``.

PR 5's convention: JAX runs in float32 by default, and the exact
eviction kernels that need double precision (stack-distance ties,
byte-exact eviction accounting) opt in with the *scoped*
``jax.enable_x64()`` context manager — never the global
``jax.config.update("jax_enable_x64", ...)`` switch, which would flip
precision (and recompile) for every other kernel in the process.  This
rule flags, in ``kernels/`` modules only:

* any *JAX* ``float64`` dtype reference (``jnp.float64``,
  ``jax.numpy.float64``, or a ``dtype="float64"`` string) outside the
  lexical body of a ``with enable_x64():`` block — host-side
  ``np.float64`` is exempt, numpy is always 64-bit capable;
* any ``config.update("jax_enable_x64", ...)`` global flip, anywhere.
"""
from __future__ import annotations

import ast
from typing import Iterable, List, Tuple

from ..core import Checker, ModuleInfo, Violation, register


def _dotted(node: ast.expr) -> str:
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
    return ".".join(reversed(parts))


def _x64_spans(tree: ast.Module) -> List[Tuple[int, int]]:
    """Line spans of ``with jax.enable_x64():`` bodies."""
    spans: List[Tuple[int, int]] = []
    for node in ast.walk(tree):
        if not isinstance(node, (ast.With, ast.AsyncWith)):
            continue
        for item in node.items:
            expr = item.context_expr
            target = expr.func if isinstance(expr, ast.Call) else expr
            if _dotted(target).split(".")[-1] == "enable_x64":
                end = node.end_lineno or node.lineno
                spans.append((node.lineno, end))
                break
    return spans


@register
class X64ScopingChecker(Checker):
    rule = "x64-scoping"
    description = ("float64 dtype use in kernels/ only inside scoped "
                   "'with enable_x64():' blocks; no global x64 flips")

    def check_module(self, mod: ModuleInfo) -> Iterable[Violation]:
        p = mod.relpath.replace("\\", "/")
        if "/kernels/" not in p and not p.startswith("kernels/"):
            return ()
        spans = _x64_spans(mod.tree)

        def scoped(line: int) -> bool:
            return any(lo <= line <= hi for lo, hi in spans)

        out: List[Violation] = []
        for node in ast.walk(mod.tree):
            if isinstance(node, ast.Attribute) and node.attr == "float64":
                root = _dotted(node).split(".")[0]
                # host numpy is exempt; only JAX dtypes need enable_x64
                if root in ("np", "numpy"):
                    continue
                if not scoped(node.lineno):
                    out.append(self.violation(
                        mod, node,
                        f"{_dotted(node)} outside a scoped "
                        f"'with enable_x64():' block — under the default "
                        f"float32 config this silently truncates to f32 "
                        f"(or requires a global flip); wrap the use in "
                        f"the scoped context manager"))
            elif isinstance(node, ast.Call):
                name = _dotted(node.func)
                if name.split(".")[-1] == "update" and node.args \
                        and isinstance(node.args[0], ast.Constant) \
                        and node.args[0].value == "jax_enable_x64":
                    out.append(self.violation(
                        mod, node,
                        "global jax_enable_x64 config flip — this "
                        "recompiles and changes precision for every "
                        "kernel in the process; use the scoped "
                        "jax.enable_x64() context manager"))
                elif isinstance(node, ast.Call):
                    for kw in node.keywords:
                        if kw.arg == "dtype" \
                                and isinstance(kw.value, ast.Constant) \
                                and kw.value.value == "float64" \
                                and not scoped(kw.value.lineno):
                            out.append(self.violation(
                                mod, kw.value,
                                "dtype=\"float64\" outside a scoped "
                                "'with enable_x64():' block"))
        return out

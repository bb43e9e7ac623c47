"""backend-dispatch: kernel implementations are reached only through the
``core/backend.py`` dispatch layer (``REPRO_ALPHA_BACKEND`` /
``REPRO_BNA_BACKEND`` / ``REPRO_PLAN_BACKEND``) so every call site gets
the backend resolution, int32 guards and caches for free.  Direct
``repro.kernels`` imports are allowed only in the dispatch layer itself,
the jitted pipeline, the kernel packages, tests, and benchmarks."""
from __future__ import annotations

import ast

from .. import FileContext, register_rule

_ALLOWED_FILES = ("repro/core/backend.py", "repro/core/pipeline.py")

_HINT = ("call through repro.core.backend (or repro.core dispatch wrappers); "
         "if this site IS the resolved dispatch target, annotate it with "
         "`# repro: allow(backend-dispatch): <one-line why>`")


def _allowed(ctx: FileContext) -> bool:
    return (any(ctx.rel.endswith(f) for f in _ALLOWED_FILES)
            or ctx.in_kernels() or ctx.in_testing() or ctx.in_benchmarks())


@register_rule("backend-dispatch",
               "repro.kernels.* imported only via core/backend.py dispatch "
               "(plus pipeline, kernel packages, tests, benchmarks)")
def _backend_dispatch(ctx: FileContext):
    if _allowed(ctx):
        return
    for node in ast.walk(ctx.tree):
        if isinstance(node, ast.Import):
            for a in node.names:
                if a.name == "repro.kernels" or \
                        a.name.startswith("repro.kernels."):
                    yield ctx.finding(
                        "backend-dispatch", node,
                        f"direct import of {a.name} bypasses backend "
                        "dispatch", _HINT)
        elif isinstance(node, ast.ImportFrom) and node.level == 0:
            mod = node.module or ""
            if mod == "repro.kernels" or mod.startswith("repro.kernels."):
                names = ", ".join(a.name for a in node.names)
                yield ctx.finding(
                    "backend-dispatch", node,
                    f"direct import of {names} from {mod} bypasses backend "
                    "dispatch", _HINT)

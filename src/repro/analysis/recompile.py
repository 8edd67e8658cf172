"""CL003/CL004 — recompile hygiene: the zero-recompile guarantee.

The serving path promises that every compilation the live phase needs
existed before the first request (warm_restart hard-fails on a nonzero
jit-cache delta).  Two code patterns silently break that promise:

CL003 (jit-in-function): a ``jax.jit`` / ``pallas_call`` constructed
inside an arbitrary function creates a fresh compilation cache per call.
Jit construction is allowed at module scope (decorators, module-level
wrappers) and inside the blessed pipeline/warmup modules that build the
compiled ladder exactly once.

CL004 (adhoc-batch-shape): a staging batch is one (B, W) buffer under
``"buf"`` (the flush's one host-to-device copy) with its ``x``, ``q``,
``mask`` and ``m_q`` views, and every live instance must come from
``alloc_batch`` / the warmed pow2 ladder.  A hand-rolled literal with
those view keys, with or without ``"buf"``, (or an ``alloc_batch`` call)
outside the bucket/warmup code is a new (B, G) shape the warmup never
compiled, or a second staging path beside the one buffer.  The trainer's
engine batches carry further keys and do not match.

Scope: ``src/repro`` only.
"""
from __future__ import annotations

import ast

from repro.analysis.core import Finding, ParsedFile, dotted_name, \
    iter_functions, walk_own_body

RULES = {
    "CL003": "jax.jit/pallas_call in function scope outside blessed modules",
    "CL004": "ad-hoc staging-batch construction outside bucket/warmup code",
}

# Modules whose whole job is building the compiled ladder / pipelines.
BLESSED_MODULE_PREFIXES = (
    "src/repro/kernels/",
    "src/repro/core/trainer.py",
    "src/repro/core/cascade.py",
    "src/repro/core/pipeline.py",
    "src/repro/launch/",
)
# Individual functions blessed outside those modules: the session's
# pipeline factory, invoked only by warmup/warm_restart.
BLESSED_FUNCTIONS = {
    ("src/repro/serving/session.py", "_make_rank"),
}

# The staging layout (serving/batching.py alloc_batch): the views, and the
# buffer they view.  Exact match only: trainer engine batches carry
# x/q/mask/m_q PLUS y/wgt/... and are a different contract.
STAGING_VIEWS = frozenset({"x", "q", "mask", "m_q"})
STAGING_BUFFER = "buf"

# Where the layout may legitimately be built.
BLESSED_SHAPE_FILES = ("src/repro/serving/batching.py",)
BLESSED_SHAPE_FUNCTIONS = {
    ("src/repro/serving/session.py", "warm_restart"),
    ("src/repro/serving/session.py", "warmup"),
}

_JIT_NAMES = {"jit", "pallas_call"}


def _is_jit_call(call: ast.Call) -> bool:
    name = dotted_name(call.func)
    if not name:
        return False
    last = name.split(".")[-1]
    if last not in _JIT_NAMES:
        return False
    # require jax.jit / pl.pallas_call / bare pallas_call — a method
    # called `.jit()` on some unrelated object is not a compilation site
    if last == "jit" and "." not in name:
        return False
    return True


def check(files: list[ParsedFile]) -> list[Finding]:
    files = [pf for pf in files
             if pf.rel.startswith("src/repro/analysis/fixtures")
             or (pf.rel.startswith("src/repro")
                 and not pf.rel.startswith("src/repro/analysis"))]
    findings: list[Finding] = []
    for pf in files:
        blessed_mod = any(pf.rel.startswith(p)
                          for p in BLESSED_MODULE_PREFIXES)
        for qual, cls, fn in iter_functions(pf.tree):
            fn_names = {fn.name, qual.split(".")[-1]}
            fn_blessed = blessed_mod or any(
                (pf.rel, n) in BLESSED_FUNCTIONS for n in fn_names)
            shape_blessed = (
                pf.rel in BLESSED_SHAPE_FILES
                or any((pf.rel, n) in BLESSED_SHAPE_FUNCTIONS
                       for n in fn_names))
            for node in walk_own_body(fn):
                if isinstance(node, ast.Call) and _is_jit_call(node) \
                        and not fn_blessed:
                    findings.append(Finding(
                        "CL003", pf.rel, node.lineno,
                        f"`{dotted_name(node.func)}` constructed inside "
                        f"`{qual}` — per-call jit objects defeat the "
                        "warmed compilation cache; build at module scope "
                        "or in the pipeline/warmup modules"))
                if isinstance(node, ast.Dict) and not shape_blessed:
                    keys = {k.value for k in node.keys
                            if isinstance(k, ast.Constant)
                            and isinstance(k.value, str)}
                    if len(keys) == len(node.keys) \
                            and keys - {STAGING_BUFFER} == STAGING_VIEWS:
                        findings.append(Finding(
                            "CL004", pf.rel, node.lineno,
                            f"hand-rolled staging batch in `{qual}` — "
                            "shapes must come from alloc_batch / the "
                            "warmed pow2 ladder or they recompile"))
                if isinstance(node, ast.Call) and not shape_blessed:
                    name = dotted_name(node.func)
                    if name and name.split(".")[-1] == "alloc_batch":
                        findings.append(Finding(
                            "CL004", pf.rel, node.lineno,
                            f"`alloc_batch` called from `{qual}` — only "
                            "the bucket/warmup code may mint batch "
                            "buffers (pool reuse + ladder shapes)"))
    return findings

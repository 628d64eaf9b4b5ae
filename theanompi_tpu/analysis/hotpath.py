"""tmcheck rule family 4: the JAX hot-path sanitizer.

Two scopes, two failure modes:

**Host-side hot loops (TM104/TM105).**  Functions seeded by name
(``registry.HOT_EXACT``/``HOT_SUBSTR``: the decode/prefill/step
family) or marked ``# tmcheck: hot`` drive jitted executables from
Python.  The discipline PR 6's chunked-prefill postmortem bought
(no per-step value fences): dispatch stays
async; at most ONE host sync per call, after the loop.  So:

- TM104 fires on a host-sync fence — ``int()``/``float()`` of a
  device-derived value, ``np.asarray``/``np.array`` of one — **inside
  a loop** of a hot function (the per-chunk/per-token fence that
  serializes every dispatch round-trip).  ``.item()``,
  ``block_until_ready`` and ``jax.device_get`` are flagged anywhere
  in a hot function: the first is a synchronous round trip by
  construction, the second a barrier by definition.  A value is
  "device-derived" when it flows (intra-function) from a call rooted
  at ``jnp``/``jax``/``lax`` or through a jit-built callable
  (function text containing ``jit``).
- TM105 fires when a shape argument of ``jnp.zeros/ones/full/empty/
  arange`` or ``reshape`` references a fence-derived Python value (a
  name bound from ``int()``/``float()``/``.item()`` of a device
  value): data-dependent shapes mint a fresh executable per distinct
  value, defeating the one-compile decode discipline.  Bucketed
  shapes (quantized host ints) pass.

**Traced bodies (TM104/TM106).**  Functions that BECOME jitted/
scanned code — decorated with ``jit``/``remat``/…, or passed by name
to ``jax.jit``/``lax.scan``/``lax.while_loop``/… anywhere in the same
file — execute at trace time.  There, ``time.time``/``time.monotonic``
/``datetime.now`` and host RNG (``random.*``, ``np.random.*``) burn a
trace-time constant into the compiled artifact (TM106), and
``.item()``/``block_until_ready`` force a concretization that either
crashes on tracers or silently constant-folds (TM104).  Functions
defined INSIDE a traced body are traced too.

Functions named ``test_*`` are exempt from host-hot seeding: tests
fence deliberately to assert values.
"""

from __future__ import annotations

import ast
import re

from theanompi_tpu.analysis.core import Finding, SourceFile
from theanompi_tpu.analysis.registry import (
    HOT_EXACT,
    HOT_SUBSTR,
    TRACED_WRAPPERS,
)

_DEVICE_ROOT_RE = re.compile(r"^(jnp|jax|lax)\b")
_SHAPE_FNS = frozenset({
    "zeros", "ones", "full", "empty", "arange", "reshape",
    "broadcast_to",
})
_WALLCLOCK = {
    ("time", "time"), ("time", "monotonic"), ("time", "perf_counter"),
    ("datetime", "now"), ("datetime", "utcnow"),
}
_FN_DEFS = (ast.FunctionDef, ast.AsyncFunctionDef)


def _leaf(func: ast.AST) -> str | None:
    if isinstance(func, ast.Attribute):
        return func.attr
    if isinstance(func, ast.Name):
        return func.id
    return None


def _is_hot_name(name: str) -> bool:
    if name.startswith("test_"):
        return False
    low = name.lower()
    return name in HOT_EXACT or any(s in low for s in HOT_SUBSTR)


def _walk_pruned(node: ast.AST):
    """Yield descendants of ``node`` WITHOUT entering nested function
    or lambda scopes (their bodies have their own verdicts)."""
    for child in ast.iter_child_nodes(node):
        if isinstance(child, _FN_DEFS + (ast.Lambda,)):
            continue
        yield child
        yield from _walk_pruned(child)


def _nested_defs(fn: ast.AST):
    """Function defs whose nearest enclosing function is ``fn``."""
    out = []

    def rec(node):
        for child in ast.iter_child_nodes(node):
            if isinstance(child, _FN_DEFS):
                out.append(child)
            else:
                rec(child)

    rec(fn)
    return out


def collect_traced_names(sf: SourceFile) -> set[str]:
    """Function names that become traced bodies in this file: passed
    to a jit/scan/…-named wrapper, or decorated with one."""
    traced: set[str] = set()
    for node in ast.walk(sf.tree):
        if isinstance(node, ast.Call) and \
                _leaf(node.func) in TRACED_WRAPPERS:
            for a in list(node.args) + [k.value for k in node.keywords]:
                name = _leaf(a)
                if name is not None:
                    traced.add(name)
        if isinstance(node, _FN_DEFS):
            for dec in node.decorator_list:
                d = dec.func if isinstance(dec, ast.Call) else dec
                if _leaf(d) in TRACED_WRAPPERS:
                    traced.add(node.name)
    return traced


def check_file(sf: SourceFile) -> list[Finding]:
    findings: list[Finding] = []
    traced = collect_traced_names(sf)

    def visit(fn, parent_traced: bool) -> None:
        is_traced = fn.name in traced or parent_traced
        if is_traced:
            findings.extend(_check_traced(sf, fn))
        elif _is_hot_name(fn.name) or sf.hot_marked(fn.lineno):
            findings.extend(_check_host_hot(sf, fn))
        for nested in _nested_defs(fn):
            visit(nested, is_traced)

    # top-level functions: module- and class-level defs (not nested
    # inside another function — those are reached via visit())
    def toplevel(node):
        for child in ast.iter_child_nodes(node):
            if isinstance(child, _FN_DEFS):
                yield child
            elif isinstance(child, ast.ClassDef):
                yield from toplevel(child)

    for fn in toplevel(sf.tree):
        visit(fn, False)
    return findings


# ---------------------------------------------------------------------------
# host-side hot functions
# ---------------------------------------------------------------------------


def _names_in(expr: ast.AST) -> set[str]:
    return {n.id for n in ast.walk(expr) if isinstance(n, ast.Name)}


def _is_device_call(sf: SourceFile, call: ast.Call) -> bool:
    """A call whose result lives on device: rooted at jnp/jax/lax, or
    made through a jit-built callable (func text mentions jit)."""
    text = sf.src(call.func)
    if _DEVICE_ROOT_RE.match(text):
        return True
    return "jit" in text.lower()


def _expr_tainted(sf: SourceFile, expr: ast.AST, tainted: set) -> bool:
    for node in ast.walk(expr):
        if isinstance(node, ast.Call) and _is_device_call(sf, node):
            return True
        if isinstance(node, ast.Name) and node.id in tainted:
            return True
    return False


def _fence_in(sf: SourceFile, expr: ast.AST, tainted: set) -> bool:
    """Does this expression contain int()/float()/.item() of a
    device value (a host sync yielding a Python scalar)?"""
    for node in ast.walk(expr):
        if not isinstance(node, ast.Call):
            continue
        f = node.func
        if isinstance(f, ast.Name) and f.id in ("int", "float") \
                and node.args \
                and _expr_tainted(sf, node.args[0], tainted):
            return True
        if isinstance(f, ast.Attribute) and f.attr == "item":
            return True
    return False


def _taint_pass(sf: SourceFile, fn) -> tuple[set, set]:
    """(device-tainted names, fence-derived names); two passes so
    loop-carried flows settle.  Nested scopes are pruned."""
    tainted: set[str] = set()
    fenced: set[str] = set()
    for _ in range(2):
        for node in _walk_pruned(fn):
            if not isinstance(node, (ast.Assign, ast.AugAssign,
                                     ast.AnnAssign)):
                continue
            value = node.value
            if value is None:
                continue
            targets = (node.targets if isinstance(node, ast.Assign)
                       else [node.target])
            names = {
                sub.id for t in targets for sub in ast.walk(t)
                if isinstance(sub, ast.Name)
            }
            if _expr_tainted(sf, value, tainted):
                tainted |= names
            if _fence_in(sf, value, tainted):
                fenced |= names
    return tainted, fenced


def _check_host_hot(sf: SourceFile, fn) -> list[Finding]:
    out: list[Finding] = []
    tainted, fenced = _taint_pass(sf, fn)
    where = f"{fn.name} (hot path)"

    def check_call(call: ast.Call, loop_depth: int) -> None:
        f = call.func
        leaf = _leaf(f)
        if leaf in ("int", "float") and isinstance(f, ast.Name):
            if loop_depth > 0 and call.args and _expr_tainted(
                    sf, call.args[0], tainted):
                out.append(Finding(
                    sf.rel, call.lineno, "TM104",
                    f"{where}: per-iteration {leaf}() fence on a "
                    f"device value — every loop pass round-trips to "
                    f"host, serializing dispatch (the PR 6 per-chunk "
                    f"fence class); hoist the ONE sync past the loop",
                ))
        elif leaf == "item" and isinstance(f, ast.Attribute):
            out.append(Finding(
                sf.rel, call.lineno, "TM104",
                f"{where}: .item() is a synchronous device round "
                f"trip — read once after the loop, or keep the "
                f"value on device",
            ))
        elif leaf == "block_until_ready":
            out.append(Finding(
                sf.rel, call.lineno, "TM104",
                f"{where}: block_until_ready() barriers the "
                f"dispatch stream inside a hot path",
            ))
        elif leaf == "device_get":
            out.append(Finding(
                sf.rel, call.lineno, "TM104",
                f"{where}: jax.device_get() is a synchronous D2H "
                f"copy in a hot path",
            ))
        elif leaf in ("asarray", "array") and isinstance(f, ast.Attribute) \
                and isinstance(f.value, ast.Name) \
                and f.value.id in ("np", "numpy"):
            if loop_depth > 0 and call.args and _expr_tainted(
                    sf, call.args[0], tainted):
                out.append(Finding(
                    sf.rel, call.lineno, "TM104",
                    f"{where}: per-iteration np.{leaf}() of a device "
                    f"value — a blocking D2H copy every loop pass",
                ))
        elif leaf in _SHAPE_FNS:
            shape_args = list(call.args[:1]) + [
                k.value for k in call.keywords
                if k.arg in ("shape", "new_sizes", "newshape")
            ]
            for a in shape_args:
                if _names_in(a) & fenced:
                    out.append(Finding(
                        sf.rel, call.lineno, "TM105",
                        f"{where}: shape of {leaf}() depends on a "
                        f"host-fenced device value — every distinct "
                        f"value mints a new executable, defeating "
                        f"the one-compile discipline; bucket the "
                        f"size or pad to a fixed shape",
                    ))
                    break

    def walk(node: ast.AST, loop_depth: int) -> None:
        if isinstance(node, _FN_DEFS + (ast.Lambda,)):
            return
        if isinstance(node, (ast.For, ast.While)):
            for child in ast.iter_child_nodes(node):
                walk(child, loop_depth + 1)
            return
        if isinstance(node, ast.Call):
            check_call(node, loop_depth)
        for child in ast.iter_child_nodes(node):
            walk(child, loop_depth)

    for stmt in fn.body:
        walk(stmt, 0)
    return out


# ---------------------------------------------------------------------------
# traced bodies
# ---------------------------------------------------------------------------


def _check_traced(sf: SourceFile, fn) -> list[Finding]:
    out: list[Finding] = []
    for node in _walk_pruned(fn):
        if not isinstance(node, ast.Call):
            continue
        f = node.func
        if isinstance(f, ast.Attribute) and isinstance(f.value, ast.Name):
            pair = (f.value.id, f.attr)
            if pair in _WALLCLOCK:
                out.append(Finding(
                    sf.rel, node.lineno, "TM106",
                    f"{fn.name} (traced body): {pair[0]}.{pair[1]}() "
                    f"runs at TRACE time — the compiled artifact "
                    f"bakes in one stale value; pass times in as "
                    f"arguments",
                ))
                continue
        if isinstance(f, ast.Attribute):
            recv = sf.src(f.value)
            if recv == "random" or recv in ("np.random", "numpy.random"):
                out.append(Finding(
                    sf.rel, node.lineno, "TM106",
                    f"{fn.name} (traced body): host RNG "
                    f"{recv}.{f.attr}() runs once at trace time — "
                    f"use jax.random with a threaded key",
                ))
                continue
        leaf = _leaf(f)
        if leaf == "item" and isinstance(f, ast.Attribute):
            out.append(Finding(
                sf.rel, node.lineno, "TM104",
                f"{fn.name} (traced body): .item() on a tracer "
                f"either crashes or constant-folds silently",
            ))
        elif leaf == "block_until_ready":
            out.append(Finding(
                sf.rel, node.lineno, "TM104",
                f"{fn.name} (traced body): block_until_ready() has "
                f"no meaning under trace — remove it",
            ))
    return out

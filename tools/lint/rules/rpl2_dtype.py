"""RPL2xx — dtype discipline (DESIGN.md §2, §8).

The repo's contract: the device path is f32 over *mean-centered*
coordinates; exactness is recovered against f64 host oracles.  Three
rules pin the three ways that split erodes:

RPL201  float64 construction inside *jit-reachable* code of a device
        module (``kernels/``, ``core/bubble_flat.py``,
        ``core/hierarchy_jax.py``, ``core/dynamic_jax.py``).  Host-side
        f64 derivation in those same files is mandated by §2 and stays
        legal — only the traced path is f32-only.
RPL202  float32 construction anywhere in a host f64 oracle module
        (``core/bubble_tree.py``, ``core/hdbscan.py``, ``core/dynamic.py``).
RPL203  a known f32 device-handoff entry point (allowlist below) casts
        to float32 without a mean-centering subtraction first — the
        off-origin catastrophic-cancellation hazard of §2.
"""

from __future__ import annotations

import ast
import re
from typing import Iterable

from tools.lint.framework import FileContext, Finding, Rule, dotted_name

DEVICE_PATH = (
    r"(^|/)kernels/[^/]+\.py$"
    r"|(^|/)core/(bubble_flat|hierarchy_jax|dynamic_jax)\.py$"
)
HOST_ORACLE_PATH = r"(^|/)core/(bubble_tree|hdbscan|dynamic)\.py$"

# (path regex, function name) pairs that hand raw coordinates to the f32
# device path and therefore must mean-center first (DESIGN §2).
F32_HANDOFF_ENTRY_POINTS: list[tuple[str, str]] = [
    (r"(^|/)kernels/ops\.py$", "cluster_bubbles"),
    (r"(^|/)serving/query\.py$", "_build_entry"),
]

# a subtraction whose right operand looks like a centroid/origin — the
# centering idioms actually used in this repo: `x - mu`, `x - snap.center`,
# `rep - ((Ng @ rep) / Ng.sum())[None, :]`, `x -= origin`
_CENTER_SRC_RE = re.compile(r"\bmu\b|center|origin|centroid|mean\s*\(|@|\.sum\s*\(")


def _is_f32_token(node: ast.AST) -> bool:
    if isinstance(node, ast.Constant) and node.value == "float32":
        return True
    name = dotted_name(node)
    return name.rsplit(".", 1)[-1] == "float32"


def _is_f64_token(node: ast.AST) -> bool:
    if isinstance(node, ast.Constant) and node.value == "float64":
        return True
    name = dotted_name(node)
    return name.rsplit(".", 1)[-1] == "float64"


def _f32_cast_lines(fn: ast.AST) -> list[int]:
    """Lines inside ``fn`` where existing data is *cast* to f32 (``astype``
    / ``asarray`` / ``array``).  Fresh f32 buffer construction
    (``zeros``/``full``) is not a handoff of off-origin data."""
    lines: list[int] = []
    for node in ast.walk(fn):
        if isinstance(node, ast.Call):
            base = dotted_name(node.func).rsplit(".", 1)[-1]
            if base == "astype" and node.args and _is_f32_token(node.args[0]):
                lines.append(node.lineno)
            elif base in {"asarray", "array"}:
                operands = list(node.args[1:]) + [kw.value for kw in node.keywords]
                if any(_is_f32_token(a) for a in operands):
                    lines.append(node.lineno)
    return sorted(lines)


class DeviceF64Rule(Rule):
    code = "RPL201"
    name = "device-f64"
    doc = "float64 construction inside jit-reachable device code"

    def check(self, ctx: FileContext) -> Iterable[Finding]:
        if not ctx.path_matches(DEVICE_PATH):
            return
        for fn in ctx.jit.reachable_functions():
            for node in ast.walk(fn):
                hit = None
                if isinstance(node, ast.Call):
                    base = dotted_name(node.func).rsplit(".", 1)[-1]
                    operands = list(node.args) + [kw.value for kw in node.keywords]
                    if base == "astype" and operands and _is_f64_token(operands[0]):
                        hit = node
                    elif any(_is_f64_token(a) for a in operands):
                        hit = node
                if hit is not None:
                    yield ctx.finding(
                        hit,
                        self.code,
                        f"float64 inside jit-reachable `{fn.name}` — the "
                        f"device path is f32-only (DESIGN §2); derive f64 on "
                        f"the host side",
                    )


class HostOracleF32Rule(Rule):
    code = "RPL202"
    name = "oracle-f32"
    doc = "float32 construction inside a host f64 oracle module"

    def check(self, ctx: FileContext) -> Iterable[Finding]:
        if not ctx.path_matches(HOST_ORACLE_PATH):
            return
        for node in ast.walk(ctx.tree):
            if isinstance(node, (ast.Attribute, ast.Constant)) and _is_f32_token(node):
                yield ctx.finding(
                    node,
                    self.code,
                    "float32 in a host f64 oracle module — the oracles exist "
                    "to be exact (DESIGN §2); keep them f64 end to end",
                )


class UncenteredHandoffRule(Rule):
    code = "RPL203"
    name = "uncentered-f32-handoff"
    doc = "f32 device handoff without a preceding mean-centering subtraction"

    def check(self, ctx: FileContext) -> Iterable[Finding]:
        for path_re, fn_name in F32_HANDOFF_ENTRY_POINTS:
            if not ctx.path_matches(path_re):
                continue
            for fn in ast.walk(ctx.tree):
                if not (
                    isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef))
                    and fn.name == fn_name
                ):
                    continue
                casts = _f32_cast_lines(fn)
                if not casts:
                    continue
                center_line = self._first_centering_line(fn, ctx)
                for cast_line in casts:
                    if center_line is None or center_line > cast_line:
                        yield ctx.finding(
                            cast_line,
                            self.code,
                            f"entry point `{fn.name}` casts to float32 without "
                            f"mean-centering first — off-origin coordinates "
                            f"cancel catastrophically in f32 (DESIGN §2)",
                        )
                break  # only the first def with this name per file

    @staticmethod
    def _first_centering_line(fn: ast.AST, ctx: FileContext) -> int | None:
        best: int | None = None
        for node in ast.walk(fn):
            rhs = None
            if isinstance(node, ast.BinOp) and isinstance(node.op, ast.Sub):
                rhs = node.right
            elif isinstance(node, ast.AugAssign) and isinstance(node.op, ast.Sub):
                rhs = node.value
            if rhs is None:
                continue
            seg = ast.get_source_segment(ctx.source, rhs) or ""
            if _CENTER_SRC_RE.search(seg) and (best is None or node.lineno < best):
                best = node.lineno
        return best


RULES = [DeviceF64Rule(), HostOracleF32Rule(), UncenteredHandoffRule()]

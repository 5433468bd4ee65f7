"""Abstract tracing and jaxpr analysis for the audit.

Everything here works on ``jax.make_jaxpr`` output — no data execution,
no device buffers beyond the tiny concrete host arrays the registry
builders hand to the tracer.  Imports jax lazily so ``tools.audit`` can
be imported (for ``--list-entries``, contract parsing, tests of the
pure-python rules) without paying jax start-up.
"""

from __future__ import annotations

import hashlib
from dataclasses import dataclass, field
from typing import Any, Callable, Iterator

# host-callback / transfer primitives that must never appear in device
# traces (RPL502).  ``device_put`` inside a jaxpr is an implicit transfer
# pinned at trace time; the callbacks smuggle host python into the
# compiled program.
CALLBACK_PRIMITIVES = frozenset(
    {
        "pure_callback",
        "io_callback",
        "debug_callback",
        "callback",
        "infeed",
        "outfeed",
        "device_put",
        "copy_to_host",
    }
)

# dims below this are feature/tile constants, never padded L/batch
# buckets — the pow-2 rule (RPL503) ignores them
MIN_POW2_DIM = 16


def is_pow2(n: int) -> bool:
    return n > 0 and (n & (n - 1)) == 0


def dim_ok_pow2(d: int, floor: int = MIN_POW2_DIM) -> bool:
    """Padded-bucket dims are pow-2 up to the repo's sentinel idioms.

    Tolerated: pow-2 within −1/+2 (``Lp ± 1`` trash rows / merge rounds,
    ``cap + largest + trash``), multiples of the entry's bucket floor
    (flattened strips like ``(B + rk_cap) · Np``), and squares of
    pow-2-ish values ±1 (``(s_cap + 1)²`` supernode pair tables).  The
    precise leak check — a raw lattice size appearing as a dim — is
    separate (``banned_dims``)."""
    if d < max(floor, MIN_POW2_DIM):
        return True
    if is_pow2(d) or is_pow2(d - 1) or is_pow2(d + 1) or is_pow2(d - 2):
        return True
    if floor > 1 and d % floor == 0:
        return True
    r = int(d**0.5)
    for s in (r, r + 1):
        if s * s in (d, d - 1, d + 1) and (
            is_pow2(s) or is_pow2(s - 1) or is_pow2(s + 1)
        ):
            return True
    return False


def walk_eqns(jaxpr) -> Iterator[Any]:
    """Yield every eqn in a (closed) jaxpr, recursing into sub-jaxprs
    held in eqn params (pjit bodies, scan/while/cond branches, shard_map,
    pallas grids)."""
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            vals = v if isinstance(v, (list, tuple)) else [v]
            for item in vals:
                if hasattr(item, "jaxpr") and hasattr(item.jaxpr, "eqns"):
                    yield from walk_eqns(item.jaxpr)
                elif hasattr(item, "eqns"):
                    yield from walk_eqns(item)


def _source_loc(eqn) -> str:
    try:
        from jax._src import source_info_util as siu

        frame = siu.user_frame(eqn.source_info.traceback)
        if frame is None:
            return "?"
        name = frame.file_name
        for marker in ("/src/", "/repro/"):
            if marker in name:
                name = name.split(marker, 1)[-1]
                break
        return f"{name}:{frame.start_line}"
    except Exception:
        return "?"


@dataclass
class AvalHit:
    """One offending output aval: primitive, dtype/shape, source line."""

    primitive: str
    dtype: str
    shape: tuple[int, ...]
    where: str


@dataclass
class TraceResult:
    """One lattice point's trace, reduced to what the rules consume."""

    label: str
    statics_key: tuple
    signature: str = ""
    primitives: dict[str, int] = field(default_factory=dict)
    out_shapes: list[str] = field(default_factory=list)
    dims: dict[int, str] = field(default_factory=dict)  # dim → first source loc
    banned_dims: tuple[int, ...] = ()  # raw sizes that must have been padded away
    callback_hits: list[AvalHit] = field(default_factory=list)
    dense_hits: list[AvalHit] = field(default_factory=list)
    error: str | None = None
    skipped: str | None = None

    def digest(self) -> dict:
        return {"primitives": dict(sorted(self.primitives.items())), "outputs": self.out_shapes}


def _canonical(jaxpr) -> str:
    """Stable text form of a closed jaxpr for the recompile signature.

    ``jaxpr.pretty_print`` with defaults is deterministic for a fixed
    trace (var names are assigned in traversal order); two lattice
    points that bucket to the same shapes produce identical text.
    """
    return str(jaxpr)


def _is_real_transfer(eqn) -> bool:
    """``device_put`` of a trace-time constant (jnp.nonzero fill values,
    committed literals) is placement, not a transfer; flag only when a
    traced value flows in."""
    if eqn.primitive.name != "device_put":
        return True
    return any(type(v).__name__ != "Literal" for v in eqn.invars)


def trace_point(
    fn: Callable[[], Any],
    *,
    label: str,
    statics_key: tuple,
    dense_dim: int | None = None,
    banned_dims: tuple[int, ...] = (),
) -> TraceResult:
    """Trace one lattice point under the default (f32) config.

    ``fn`` is a registry builder thunk returning the ClosedJaxpr (it
    calls ``jax.make_jaxpr(...)(*args)`` itself so builders control
    statics).  ``dense_dim`` is the padded L for RPL504 scanning.
    """
    res = TraceResult(label=label, statics_key=statics_key, banned_dims=banned_dims)
    try:
        closed = fn()
    except Exception as e:  # noqa: BLE001 — every trace failure is a finding
        res.error = f"{type(e).__name__}: {e}"
        return res
    res.signature = hashlib.sha256(_canonical(closed).encode()).hexdigest()[:16]
    for ov in closed.jaxpr.outvars:
        aval = getattr(ov, "aval", None)
        if aval is not None and hasattr(aval, "shape"):
            res.out_shapes.append(f"{getattr(aval, 'dtype', '?')}{list(aval.shape)}")
    for eqn in walk_eqns(closed.jaxpr):
        name = eqn.primitive.name
        res.primitives[name] = res.primitives.get(name, 0) + 1
        if name in CALLBACK_PRIMITIVES and _is_real_transfer(eqn):
            res.callback_hits.append(AvalHit(name, "-", (), _source_loc(eqn)))
        for out in eqn.outvars:
            aval = getattr(out, "aval", None)
            if aval is None or not hasattr(aval, "shape"):
                continue
            shape = tuple(int(d) for d in aval.shape if isinstance(d, int) or hasattr(d, "__int__"))
            for d in shape:
                res.dims.setdefault(d, _source_loc(eqn))
            if dense_dim is not None and shape.count(dense_dim) >= 2:
                res.dense_hits.append(
                    AvalHit(name, str(getattr(aval, "dtype", "?")), shape, _source_loc(eqn))
                )
    return res


def probe_x64(fn: Callable[[], Any], *, label: str) -> list[AvalHit] | str:
    """Re-trace one lattice point under scoped ``enable_x64`` and return
    every float64/complex128 output aval (or an error string).

    With x64 off (the shipped config) an accidental ``astype(float64)``
    is silently canonicalized to f32 and invisible; under the scoped
    flag it surfaces as a real f64 aval.  Integer widening (int64 from
    platform-int accumulations) is deliberately ignored — the f32-only
    contract is about float math.
    """
    import jax

    hits: list[AvalHit] = []
    try:
        with jax.enable_x64():
            closed = fn()
    except Exception as e:  # noqa: BLE001
        return f"{type(e).__name__}: {e}"
    for eqn in walk_eqns(closed.jaxpr):
        for out in eqn.outvars:
            aval = getattr(out, "aval", None)
            dtype = str(getattr(aval, "dtype", ""))
            if dtype in ("float64", "complex128"):
                hits.append(
                    AvalHit(
                        eqn.primitive.name,
                        dtype,
                        tuple(getattr(aval, "shape", ())),
                        _source_loc(eqn),
                    )
                )
    return hits

"""The audited jit entry points and their trace lattices.

Each :class:`EntrySpec` names one ``# trace-contract:`` declaration and
enumerates the (L-bucket × batch-bucket × backend × mesh-shape) lattice
points to trace.  Builders construct *tiny* concrete host arrays (shape
carriers — ``make_jaxpr`` never executes the function on them) and
route raw sizes through the repo's own bucketing helpers, so the
recompile-churn gate (RPL505) exercises the real raw-size → padded-shape
mapping: two raw sizes that bucket together MUST yield byte-identical
jaxprs.

``_sl_fixed_jit`` (hierarchy_jax) is deliberately unregistered: it is a
test-only convenience wrapper whose body is ``single_linkage_fixed``,
fully covered by the ``hierarchy_fixed`` entry.

Importing this module imports jax and the pipeline modules — callers
that only need names/metadata should treat imports as expensive (the
CLI imports lazily).  Mesh lattice points carry ``min_devices``; the
driver skips them when the process has fewer devices (the CLI sets
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` up front, so a
normal ``make audit`` run always covers mesh 1/2/8).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable

import numpy as np

AUDITED_MODULES = [
    "src/repro/kernels/ops.py",
    "src/repro/core/hierarchy_jax.py",
    "src/repro/core/dynamic_jax.py",
    "src/repro/core/bubble_flat.py",
    "src/repro/serving/query.py",
]

_DIM = 16  # feature dim used by every builder (pow-2, pallas-lane friendly)


@dataclass(frozen=True)
class LatticePoint:
    """One abstract trace of one entry point.

    ``statics_key`` are the *bucket* coordinates: every point sharing a
    key must produce a byte-identical jaxpr (RPL505).  ``dense_dim``
    switches the RPL504 (L, L) scan on for this point, with the given L.
    """

    label: str
    statics_key: tuple
    build: Callable[[], Any]  # () -> jax.core.ClosedJaxpr
    dense_dim: int | None = None
    banned_dims: tuple[int, ...] = ()  # raw sizes that must never be a dim
    x64: bool = False  # run the RPL501 f64 probe on this point
    min_devices: int = 1


@dataclass(frozen=True)
class EntrySpec:
    name: str
    module: str  # repo-relative path carrying the # trace-contract: line
    points: tuple[LatticePoint, ...]
    pow2_floor: int = 64  # RPL503 checks dims >= this (bucket scale)

    @property
    def declared_buckets(self) -> int:
        return len({p.statics_key for p in self.points})


def _pow2(n: int, floor: int = 8) -> int:
    return max(floor, 1 << (max(n - 1, 1)).bit_length())


def _banned(raw: int, bucket: int) -> tuple[int, ...]:
    """A raw size that was supposed to be padded away must not surface
    as any traced dim (RPL503's precise bucket-leak check)."""
    return (raw,) if raw != bucket else ()


def _rep_args(L_raw: int):
    """Padded offline-pipeline inputs for a raw summary size, using the
    same pad rule as the ``ops.offline_*`` host wrappers."""
    import jax.numpy as jnp

    from repro.kernels.ops import _PAD_COORD, _pow2_rows

    Lp = _pow2_rows(L_raw)
    rep = np.zeros((Lp, _DIM), np.float32)
    rep[:L_raw, 0] = np.arange(L_raw)
    rep[L_raw:] = _PAD_COORD
    n_b = np.zeros(Lp, np.float32)
    n_b[:L_raw] = 1.0
    ext = np.zeros(Lp, np.float32)
    return (
        jnp.asarray(rep),
        jnp.asarray(n_b),
        jnp.asarray(ext),
        jnp.asarray(L_raw, jnp.int32),
        jnp.asarray(5.0, jnp.float32),
    )


def _offline_point(L_raw: int, backend: str, mesh_size: int = 1) -> LatticePoint:
    def build():
        import jax

        from repro.kernels import ops

        args = _rep_args(L_raw)
        mesh = jax.make_mesh((mesh_size,), ("data",)) if mesh_size > 1 else None
        kw: dict[str, Any] = {}
        use_ref = backend != "pallas"
        if backend == "spatial":
            kw = {"spatial": True, "with_w": False}
        if mesh is not None:
            kw["mesh"] = mesh
        return jax.make_jaxpr(
            lambda r, n, e, nv, mcs: ops._offline_pipeline(r, n, e, nv, mcs, 5, use_ref, **kw)
        )(*args)

    from repro.kernels.ops import _pow2_rows

    Lp = _pow2_rows(L_raw)
    pruned = backend == "spatial" or mesh_size > 1
    return LatticePoint(
        label=f"L{Lp}-{backend}-mesh{mesh_size}-raw{L_raw}",
        statics_key=(Lp, backend, mesh_size),
        build=build,
        dense_dim=Lp if pruned else None,
        banned_dims=_banned(L_raw, Lp),
        x64=(L_raw == Lp and mesh_size == 1),
        min_devices=mesh_size,
    )


def _device_table_point(L_raw: int, mesh_size: int = 1) -> LatticePoint:
    def build():
        import jax
        import jax.numpy as jnp

        from repro.kernels import ops

        Lp = _pow2(L_raw)
        f = lambda shape: jnp.zeros(shape, jnp.float32)  # noqa: E731
        args = (
            f((Lp, _DIM)),
            f((Lp, _DIM)),
            f(Lp),
            f(Lp),
            jnp.ones(Lp, jnp.float32),
            jnp.asarray(np.arange(Lp) < L_raw),
            jnp.asarray(5.0, jnp.float32),
        )
        mesh = jax.make_mesh((mesh_size,), ("data",)) if mesh_size > 1 else None
        kw = {"mesh": mesh} if mesh is not None else {}
        return jax.make_jaxpr(
            lambda *a: ops._device_table_pipeline(*a, 5, True, Lp, **kw))(*args)

    Lp = _pow2(L_raw)
    return LatticePoint(
        label=f"L{Lp}-mesh{mesh_size}-raw{L_raw}",
        statics_key=(Lp, mesh_size),
        build=build,
        dense_dim=Lp if mesh_size > 1 else None,
        banned_dims=_banned(L_raw, Lp),
        x64=(L_raw == Lp and mesh_size == 1),
        min_devices=mesh_size,
    )


def _dyn_state(capacity: int = 64):
    from repro.core import dynamic_jax as dj

    return dj.init_state(capacity, _DIM, 5)


def _dyn_batch(n_raw: int):
    """Pad a raw batch the way ``DynamicJaxHDBSCAN._pad_block`` does."""
    import jax.numpy as jnp

    from repro.core.dynamic_jax import DynamicJaxHDBSCAN

    bp = max(DynamicJaxHDBSCAN.MIN_BLOCK, 1 << (max(n_raw - 1, 1)).bit_length())
    pts = np.zeros((bp, _DIM), np.float32)
    slots = np.zeros(bp, np.int32)
    slots[:n_raw] = np.arange(n_raw)
    valid = np.arange(bp) < n_raw
    return bp, (jnp.asarray(pts), jnp.asarray(slots), jnp.asarray(valid))


def _dyn_insert_point(n_raw: int) -> LatticePoint:
    def build():
        import jax

        from repro.core import dynamic_jax as dj

        st = _dyn_state()
        _, (pts, slots, valid) = _dyn_batch(n_raw)
        return jax.make_jaxpr(
            lambda s, p, sl, v: dj.insert_batch(s, p, sl, v, min_pts=5, rk_cap=16)
        )(st, pts, slots, valid)

    bp, _ = _dyn_batch(n_raw)
    return LatticePoint(
        label=f"B{bp}-raw{n_raw}",
        statics_key=(64, bp),
        build=build,
        banned_dims=_banned(n_raw, bp),
        x64=(n_raw == bp),
    )


def _dyn_delete_point(n_raw: int) -> LatticePoint:
    def build():
        import jax

        from repro.core import dynamic_jax as dj

        st = _dyn_state()
        _, (_, slots, valid) = _dyn_batch(n_raw)
        return jax.make_jaxpr(
            lambda s, sl, v: dj.delete_batch(s, sl, v, min_pts=5, rk_cap=16, s_cap=16)
        )(st, slots, valid)

    bp, _ = _dyn_batch(n_raw)
    return LatticePoint(
        label=f"B{bp}-raw{n_raw}",
        statics_key=(64, bp),
        build=build,
        banned_dims=_banned(n_raw, bp),
        x64=(n_raw == bp),
    )


def _dyn_rebuild_point(capacity: int) -> LatticePoint:
    def build():
        import jax

        from repro.core import dynamic_jax as dj

        st = _dyn_state(capacity)
        return jax.make_jaxpr(lambda s: dj.rebuild(s, min_pts=5))(st)

    return LatticePoint(
        label=f"cap{capacity}",
        statics_key=(capacity,),
        build=build,
        x64=True,
    )


def _flat_args(Lp: int, n_raw: int):
    import jax.numpy as jnp

    from repro.core.bubble_flat import _pow2

    bp = _pow2(n_raw)
    f = lambda shape: jnp.zeros(shape, jnp.float32)  # noqa: E731
    table = (
        f((Lp, _DIM)),
        f((Lp, _DIM)),
        f(Lp),
        f(Lp),
        jnp.ones(Lp, jnp.float32),
        jnp.ones(Lp, bool),
    )
    Xc = f((bp, _DIM))
    valid = jnp.asarray(np.arange(bp) < n_raw)
    return bp, table, Xc, valid


def _flat_insert_point(n_raw: int) -> LatticePoint:
    def build():
        import jax
        import jax.numpy as jnp

        from repro.core import bubble_flat as bf

        _, table, Xc, valid = _flat_args(64, n_raw)
        return jax.make_jaxpr(lambda *a: bf._flat_insert(*a, 16, True, False))(
            *table, Xc, valid, jnp.asarray(8.0, jnp.float32)
        )

    bp, _, _, _ = _flat_args(64, n_raw)
    return LatticePoint(
        label=f"L64-B{bp}-raw{n_raw}",
        statics_key=(64, bp),
        build=build,
        banned_dims=_banned(n_raw, bp),
        x64=(n_raw == bp),
    )


def _flat_patch_point(n_raw: int) -> LatticePoint:
    def build():
        import jax
        import jax.numpy as jnp

        from repro.core import bubble_flat as bf

        bp, table, _, _ = _flat_args(64, n_raw)
        idx = jnp.zeros(bp, jnp.int32)
        f = lambda shape: jnp.zeros(shape, jnp.float32)  # noqa: E731
        return jax.make_jaxpr(lambda *a: bf._flat_patch(*a))(
            *table, idx, f((bp, _DIM)), f(bp), f(bp), jnp.ones(bp, bool)
        )

    bp, _, _, _ = _flat_args(64, n_raw)
    return LatticePoint(
        label=f"L64-B{bp}-raw{n_raw}", statics_key=(64, bp), build=build, x64=(n_raw == bp)
    )


def _flat_delete_point(n_raw: int) -> LatticePoint:
    def build():
        import jax
        import jax.numpy as jnp

        from repro.core import bubble_flat as bf

        bp, table, Xc, valid = _flat_args(64, n_raw)
        slots = jnp.zeros(bp, jnp.int32)
        return jax.make_jaxpr(lambda *a: bf._flat_delete(*a))(
            *table, slots, Xc, valid, jnp.asarray(1.0, jnp.float32)
        )

    bp, _, _, _ = _flat_args(64, n_raw)
    return LatticePoint(
        label=f"L64-B{bp}-raw{n_raw}", statics_key=(64, bp), build=build, x64=(n_raw == bp)
    )


def _query_point(n_raw: int, Lp: int = 64) -> LatticePoint:
    def build():
        import jax
        import jax.numpy as jnp

        from repro.serving import query as q

        bq = q._bucket(n_raw)
        f = lambda shape: jnp.zeros(shape, jnp.float32)  # noqa: E731
        return jax.make_jaxpr(lambda *a: q._fused_query(*a, True))(
            f((bq, _DIM)),
            f((Lp, _DIM)),
            jnp.zeros(Lp, jnp.int32),
            f(Lp),
            jnp.ones(Lp, jnp.float32),
        )

    from repro.serving.query import _bucket

    bq = _bucket(n_raw)
    return LatticePoint(
        label=f"L{Lp}-B{bq}-raw{n_raw}",
        statics_key=(Lp, bq),
        build=build,
        banned_dims=_banned(n_raw, bq),
        x64=(n_raw == bq),
    )


def _query_grid_point(n_raw: int, Lp: int = 256) -> LatticePoint:
    def build():
        import jax
        import jax.numpy as jnp

        from repro.kernels.grid import build_grid
        from repro.serving import query as q

        bq = q._bucket(n_raw)
        pts = np.random.RandomState(0).rand(Lp, _DIM).astype(np.float32)
        gi = build_grid(pts, np.ones(Lp, bool))
        f = lambda shape: jnp.zeros(shape, jnp.float32)  # noqa: E731
        return jax.make_jaxpr(lambda *a: q._fused_query_grid(*a))(
            f((bq, _DIM)), gi, jnp.zeros(Lp, jnp.int32), f(Lp), jnp.ones(Lp, jnp.float32)
        )

    from repro.serving.query import _bucket

    bq = _bucket(n_raw)
    return LatticePoint(
        label=f"L{Lp}-B{bq}-raw{n_raw}",
        statics_key=(Lp, bq),
        build=build,
        dense_dim=Lp,
        banned_dims=_banned(n_raw, bq),
        x64=(n_raw == bq),
    )


def _incremental_point(capacity: int) -> LatticePoint:
    def build():
        import jax
        import jax.numpy as jnp

        from repro.kernels import ops

        st = _dyn_state(capacity)
        return jax.make_jaxpr(lambda *a: ops._incremental_pipeline(*a))(
            st.X,
            st.mst_u,
            st.mst_v,
            st.mst_raw,
            st.mst_valid,
            st.cd,
            st.alive,
            jnp.asarray(capacity, jnp.int32),
            jnp.asarray(5.0, jnp.float32),
        )

    return LatticePoint(label=f"cap{capacity}", statics_key=(capacity,), build=build, x64=True)


def _hierarchy_point(L_raw: int) -> LatticePoint:
    def build():
        import jax
        import jax.numpy as jnp

        from repro.core import hierarchy_jax as hj

        Lp = _pow2(L_raw)
        eu = jnp.zeros(Lp, jnp.int32)
        ev = jnp.asarray(np.minimum(np.arange(Lp) + 1, Lp - 1).astype(np.int32))
        ew = jnp.ones(Lp, jnp.float32)
        valid = jnp.asarray(np.arange(Lp) < L_raw - 1)
        return jax.make_jaxpr(lambda *a: hj.hierarchy_fixed(*a, method="eom"))(
            eu,
            ev,
            ew,
            valid,
            jnp.asarray(L_raw, jnp.int32),
            jnp.ones(Lp, jnp.float32),
            jnp.asarray(5.0, jnp.float32),
        )

    Lp = _pow2(L_raw)
    return LatticePoint(
        label=f"L{Lp}-raw{L_raw}",
        statics_key=(Lp,),
        build=build,
        banned_dims=_banned(L_raw, Lp),
        x64=(L_raw == Lp),
    )


def build_registry() -> list[EntrySpec]:
    return [
        EntrySpec(
            name="offline_pipeline",
            module="src/repro/kernels/ops.py",
            points=(
                _offline_point(48, "jnp"),
                _offline_point(64, "jnp"),
                _offline_point(200, "jnp"),
                _offline_point(64, "pallas"),
                _offline_point(256, "pallas"),
                _offline_point(64, "spatial"),
                _offline_point(256, "spatial"),
                _offline_point(64, "jnp", mesh_size=2),
                _offline_point(64, "jnp", mesh_size=8),
            ),
        ),
        EntrySpec(
            name="device_table_pipeline",
            module="src/repro/kernels/ops.py",
            points=(
                _device_table_point(48),
                _device_table_point(64),
                _device_table_point(256),
                _device_table_point(64, mesh_size=2),
            ),
        ),
        EntrySpec(
            name="incremental_pipeline",
            module="src/repro/kernels/ops.py",
            points=(_incremental_point(64),),
        ),
        EntrySpec(
            name="hierarchy_fixed",
            module="src/repro/core/hierarchy_jax.py",
            points=(_hierarchy_point(48), _hierarchy_point(64), _hierarchy_point(256)),
        ),
        EntrySpec(
            name="dyn_insert_batch",
            module="src/repro/core/dynamic_jax.py",
            points=(_dyn_insert_point(6), _dyn_insert_point(8), _dyn_insert_point(12)),
            pow2_floor=8,
        ),
        EntrySpec(
            name="dyn_delete_batch",
            module="src/repro/core/dynamic_jax.py",
            points=(_dyn_delete_point(6), _dyn_delete_point(8)),
            pow2_floor=8,
        ),
        EntrySpec(
            name="dyn_rebuild",
            module="src/repro/core/dynamic_jax.py",
            points=(_dyn_rebuild_point(64),),
        ),
        EntrySpec(
            name="flat_insert",
            module="src/repro/core/bubble_flat.py",
            points=(_flat_insert_point(20), _flat_insert_point(32)),
        ),
        EntrySpec(
            name="flat_patch",
            module="src/repro/core/bubble_flat.py",
            points=(_flat_patch_point(8),),
        ),
        EntrySpec(
            name="flat_delete",
            module="src/repro/core/bubble_flat.py",
            points=(_flat_delete_point(32),),
        ),
        EntrySpec(
            name="fused_query",
            module="src/repro/serving/query.py",
            points=(_query_point(6), _query_point(10), _query_point(16)),
            pow2_floor=8,
        ),
        EntrySpec(
            name="fused_query_grid",
            module="src/repro/serving/query.py",
            points=(_query_grid_point(16),),
            pow2_floor=8,
        ),
    ]

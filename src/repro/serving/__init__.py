"""Serve plane: streaming engine, fused query path, multi-tenant router.

Deadlock freedom is by construction: every nested acquisition must follow
the declared total order below (machine-checked by repro-lint RPL303).
An outer batcher dispatch may resolve a tenant, which may publish or read
a snapshot, which may populate the version-keyed device cache — never the
reverse.
"""
# lock-order: QueryBatcher._dispatch -> TenantRouter._lock -> StreamingClusterEngine._snapshot_lock -> SnapshotDeviceCache._lock

from .batcher import HostBatcher
from .query import QueryBatcher, QueryEngine, QueryResult, SnapshotDeviceCache
from .stream import ClusterSnapshot, StalenessPolicy, StreamingClusterEngine, Ticket
from .tenants import TenantRouter

__all__ = [
    "HostBatcher",
    "ClusterSnapshot",
    "QueryBatcher",
    "QueryEngine",
    "QueryResult",
    "SnapshotDeviceCache",
    "StalenessPolicy",
    "StreamingClusterEngine",
    "TenantRouter",
    "Ticket",
]

"""Sharded serving runtime: key-partitioned multi-chip execution behind
the normal InputHandler/source/sink API.

The package owns three concerns that used to be scattered or missing:

- `router` — the canonical key->shard/state-row layout (staging-time
  grouping, purger resets, dirty-mask marking all route through it);
- `snapshot` — mesh-resize re-bucketing so a snapshot taken on an N-way
  mesh restores onto an M-way mesh (including M=1);
- `metrics` — per-shard state-bytes/balance accounting for /metrics,
  /healthz, and EXPLAIN's sharding node.

Entry point for serving stays `SiddhiManager.create_siddhi_app_runtime
(app, mesh=Mesh(devices, ('shard',)))`: the runtime then routes every
ingest path (sync, @async, @pipeline, @fuse) across the mesh with output
byte-identical to the unsharded runtime.
"""
from .router import (ShardRouter, group_router_for,  # noqa: F401
                     router_for, shard_count)
from .snapshot import (needs_rebucket, query_layout,  # noqa: F401
                       rebucket_rows, rebucket_selector, rebucket_state)
from .metrics import (explain_node, shard_report,  # noqa: F401
                      shard_state_bytes, step_collectives)

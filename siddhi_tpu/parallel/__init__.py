"""Multi-device execution: mesh sharding for partitioned state
(parallel/mesh.py, the dryrun-proven routed step) and the first-class
`@app:shard` runtime mode (parallel/shard.py)."""

from siddhi_tpu.parallel.shard import (  # noqa: F401
    ShardRuntime,
    resolve_shard_annotation,
    shard_env_override,
)

from tpusim.parallel.shard_engine import make_shardmap_table_replay
from tpusim.parallel.sharding import (
    make_mesh,
    make_sharded_replay,
    make_sharded_table_replay,
    pad_nodes,
    shard_state,
    state_sharding,
)

__all__ = [
    "make_mesh",
    "make_sharded_replay",
    "make_sharded_table_replay",
    "make_shardmap_table_replay",
    "pad_nodes",
    "shard_state",
    "state_sharding",
]

# Virtual-mesh bootstrap (virtual_cpu_devices) does not live or re-export
# here: a caller needs it before it imports anything heavy. Import it from
# tpusim.virtual_mesh instead.

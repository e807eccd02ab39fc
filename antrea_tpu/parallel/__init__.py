from .mesh import (  # noqa: F401
    make_mesh,
    make_sharded_classifier,
    make_sharded_pipeline,
    make_sharded_pipeline_full,
    shard_of_tuples,
    shard_rule_set,
    shard_state,
)
from .meshpath import MeshDatapath, MeshSlowPath  # noqa: F401
from .reshard import RESHARD_MANIFEST, ReshardPlane  # noqa: F401

"""Online retrieval serving: the fault-tolerant mesh (one front end for
one device, a sharded catalogue and a replicated one), request
micro-batching, live ψ publish from training, and the IVF approximate
tier with quantized ψ storage (see serve/README.md for the operations
guide)."""
from repro.serve.ann import (  # noqa: F401
    AnnConfig,
    PsiIndex,
    build_shard_indexes,
    fold_delta_indexes,
    kmeans,
)
from repro.serve.batcher import MicroBatcher  # noqa: F401
from repro.serve.cluster import (  # noqa: F401
    PsiShardSet,
    TopKResult,
    shard_map_topk,
    shard_psi,
)
from repro.serve.mesh import (  # noqa: F401
    FaultInjector,
    FaultTolerantRetrievalMesh,
    ReplicaSet,
    RetryPolicy,
    ShardHealthMonitor,
)
from repro.serve.publish import (  # noqa: F401
    PsiPublisher,
    StagedRollout,
    VersionedTable,
    apply_delta,
    dense_table,
)
from repro.kernels.topk_score.ops import exclude_ids_from_lists  # noqa: F401
from repro.kernels.topk_score.ref import retrieval_topk  # noqa: F401

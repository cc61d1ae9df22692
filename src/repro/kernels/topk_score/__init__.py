from repro.kernels.topk_score.kernel import excl_walk_rounds  # noqa: F401
from repro.kernels.topk_score.ops import topk_merge_shards, topk_score  # noqa: F401
from repro.kernels.topk_score.ref import topk_score_ref  # noqa: F401

from antidote_tpu.materializer.fold import fold_batch, fold_key, eager_fold_batch
from antidote_tpu.materializer.pallas_kernels import (
    counter_fold,
    orset_presence,
)

__all__ = [
    "fold_batch",
    "fold_key",
    "eager_fold_batch",
    "counter_fold",
    "orset_presence",
]

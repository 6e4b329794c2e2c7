from dbsp_tpu.zset.batch import (Batch, ColumnBlock, concat_batches,
                                 bucket_cap, WEIGHT_DTYPE)
from dbsp_tpu.zset import kernels

__all__ = ["Batch", "ColumnBlock", "concat_batches", "bucket_cap", "WEIGHT_DTYPE", "kernels"]

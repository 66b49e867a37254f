from . import sharding
from .compression import (compress_tree, make_error_feedback_compressor,
                          compression_ratio)
from .seqparallel import seq_parallel_ssd

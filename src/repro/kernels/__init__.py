from .ops import (matmul, flash_attention, ssd_scan, ssd_state_update,
                  decode_attention)
from . import ref

"""TPU kernels (pallas) for the hot ops.

The compute path of this framework is JAX/XLA; where XLA's fusions are not
enough, ops here drop to hand-written pallas TPU kernels. Every kernel has
an interpret-mode path so the full test suite runs on CPU.
"""

from .autotune import tune_flash_blocks
from .flash_attention import flash_attention, make_flash_attention
from .paged_attention import (
    kv_blocks_walked,
    pages_per_block,
    paged_attention,
    paged_attention_reference,
    paged_prefill_attention,
)
from .segments import normalize_segment_ids

__all__ = [
    "flash_attention",
    "kv_blocks_walked",
    "make_flash_attention",
    "normalize_segment_ids",
    "pages_per_block",
    "paged_attention",
    "paged_attention_reference",
    "paged_prefill_attention",
    "tune_flash_blocks",
]

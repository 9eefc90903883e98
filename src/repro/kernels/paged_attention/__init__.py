from repro.kernels.paged_attention.kernel import (live_blocks,
                                                  paged_decode_attention)
from repro.kernels.paged_attention.ops import (paged_attention,
                                               pool_attention_xla)
from repro.kernels.paged_attention.ref import paged_attention_ref

__all__ = ["live_blocks", "paged_decode_attention", "paged_attention",
           "pool_attention_xla", "paged_attention_ref"]

from deepspeed_tpu.ops.attention.flash import (attention_reference,
                                               flash_attention)
from deepspeed_tpu.ops.attention.masked_flash import (BlockMask,
                                                      masked_flash_attention,
                                                      masked_flash_cost)
from deepspeed_tpu.ops.attention.paged import (paged_decode_attention,
                                               paged_decode_supported)
from deepspeed_tpu.ops.attention.ring import ring_attention

__all__ = ["attention_reference", "flash_attention", "ring_attention",
           "paged_decode_attention", "paged_decode_supported",
           "BlockMask", "masked_flash_attention", "masked_flash_cost"]

"""Block-sparse attention subsystem (reference:
deepspeed/ops/sparse_attention/__init__.py) — sparsity layout configs,
the fused block-sparse attention entry, and attention modules."""

from deepspeed_tpu.ops.sparse_attention.sparsity_config import (  # noqa
    SparsityConfig, DenseSparsityConfig, FixedSparsityConfig,
    VariableSparsityConfig, BigBirdSparsityConfig,
    BSLongformerSparsityConfig, sparsity_config_from_dict)
from deepspeed_tpu.ops.sparse_attention.blocksparse import (  # noqa
    block_sparse_attention, block_sparse_attention_reference,
    build_row_luts, build_col_luts, layout_additive_mask)
from deepspeed_tpu.ops.sparse_attention.sparse_self_attention import (  # noqa
    SparseSelfAttention, BertSparseSelfAttention,
    init_bert_sparse_self_attention_params, SparseAttentionUtils)
from deepspeed_tpu.ops.sparse_attention.ops import (  # noqa
    MatMul, Softmax)

"""apex_tpu.ops — the fused op library (Pallas TPU kernels + XLA references).

Reference equivalents live in csrc/ and apex/contrib/csrc/ (see SURVEY.md
§2.2-2.3). Every op has a pure-jnp/lax implementation (always available,
XLA-fused) and, where profitable, a Pallas TPU kernel; ``_pallas_utils``
decides which of the two runs.
"""

from apex_tpu.ops.dense import (  # noqa: F401
    fused_dense_function,
    fused_dense_gelu_dense_function,
)
from apex_tpu.ops.layer_norm import (  # noqa: F401
    fused_layer_norm,
    fused_rms_norm,
)
from apex_tpu.ops.flat_adam import flat_adam_update  # noqa: F401
from apex_tpu.ops.collective_matmul import (  # noqa: F401
    all_gather_matmul,
    matmul_all_reduce,
    matmul_reduce_scatter,
    ring_all_gather,
    ring_reduce_scatter,
)
from apex_tpu.ops.rope import (  # noqa: F401
    fused_apply_rotary_pos_emb,
    fused_apply_rotary_pos_emb_2d,
    fused_apply_rotary_pos_emb_cached,
    fused_apply_rotary_pos_emb_ragged,
    fused_apply_rotary_pos_emb_thd,
)
from apex_tpu.ops.paged_attention import (  # noqa: F401
    paged_attention_reference,
    ragged_paged_attention,
)
from apex_tpu.ops.softmax import (  # noqa: F401
    generic_scaled_masked_softmax,
    scaled_masked_softmax,
    scaled_softmax,
    scaled_upper_triang_masked_softmax,
)
from apex_tpu.ops.swiglu import (  # noqa: F401
    fused_bias_swiglu,
    fused_bias_swiglu_paired,
)
from apex_tpu.ops.xentropy import softmax_cross_entropy_loss  # noqa: F401

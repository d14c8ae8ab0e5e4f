"""Fused softmax cross-entropy (reference: apex/contrib/xentropy)."""

from apex_tpu.contrib.xentropy.linear_xentropy import (  # noqa: F401
    linear_cross_entropy, weighted_linear_cross_entropy,
)
from apex_tpu.contrib.xentropy.softmax_xentropy import (  # noqa: F401
    SoftmaxCrossEntropyLoss, select_label_logits,
    softmax_cross_entropy_loss,
)

"""Benchmark/example models (the reference keeps these in examples/;
here they are first-class so the benchmark entrypoints and the graft
harness share one implementation)."""

from apex_tpu.models.resnet import (  # noqa: F401
    ResNet, resnet18, resnet34, resnet50, resnet101, resnet152,
)
from apex_tpu.models.transformer import TransformerLM  # noqa: F401
from apex_tpu.models.hybrid_lm import HybridLM  # noqa: F401
from apex_tpu.models.vit import (  # noqa: F401
    ViT, vit_tiny, vit_small, vit_b16, vit_l16,
)
from apex_tpu.models.seq2seq import Seq2SeqTransformer  # noqa: F401

"""Reference consumer models for the input pipeline's examples/benchmarks (the analog of
the reference's examples/mnist and examples/imagenet model code, re-done in flax)."""

from petastorm_tpu.models.mnist import MnistCNN  # noqa: F401
from petastorm_tpu.models.resnet import ResNet50  # noqa: F401
from petastorm_tpu.models.transformer import TransformerLM, next_token_loss  # noqa: F401
from petastorm_tpu.models.moe import (MoEMlp, MoEBlock, MoETransformerLM,  # noqa: F401
                                      DroplessMoE, expert_counters,
                                      expert_partition_specs, moe_aux_total)
from petastorm_tpu.models.deepseek import (DeepSeekConfig, DeepSeekV3LM,  # noqa: F401
                                           MLAttention, RMSNorm)

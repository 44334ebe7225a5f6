"""Model zoo (``reference:apex/transformer/testing/standalone_*.py`` +
the imagenet example model)."""

from apex_tpu.models.bert import BertConfig, BertModel  # noqa: F401
from apex_tpu.models.gpt import GPTConfig, GPTModel  # noqa: F401
from apex_tpu.models.pattern_decoder import (  # noqa: F401
    PatternDecoder, PatternDecoderConfig)
from apex_tpu.models.resnet import (  # noqa: F401
    Bottleneck, ResNet50, ResNetConfig)

__all__ = ["GPTConfig", "GPTModel", "BertConfig", "BertModel",
           "PatternDecoderConfig", "PatternDecoder",
           "ResNetConfig", "ResNet50", "Bottleneck"]

"""InputType — shape metadata for automatic nIn inference and preprocessor
insertion.

Analog of the reference's org.deeplearning4j.nn.conf.inputs.InputType (used
by MultiLayerConfiguration.Builder.setInputType and InputTypeUtil). One
deliberate TPU-first difference: convolutional activations are NHWC
(batch, height, width, channels) — XLA's preferred TPU layout — where the
reference uses NCHW. Keras/DL4J import paths transpose at the boundary.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

from deeplearning4j_tpu.nn.conf.serde import register_config


@register_config("input.feedforward")
@dataclasses.dataclass
class FeedForwardInput:
    size: int

    @property
    def kind(self):
        return "ff"

    def arity(self):
        return self.size


@register_config("input.recurrent")
@dataclasses.dataclass
class RecurrentInput:
    size: int
    timesteps: Optional[int] = None  # None = variable length

    @property
    def kind(self):
        return "rnn"

    def arity(self):
        return self.size


@register_config("input.convolutional")
@dataclasses.dataclass
class ConvolutionalInput:
    """NHWC activation shape (height, width, channels)."""

    height: int
    width: int
    channels: int

    @property
    def kind(self):
        return "cnn"

    def arity(self):
        return self.height * self.width * self.channels


@register_config("input.convolutional_flat")
@dataclasses.dataclass
class ConvolutionalFlatInput:
    """Flattened image rows (e.g. MNIST 784) to be reshaped to NHWC.
    Reference: InputType.convolutionalFlat."""

    height: int
    width: int
    channels: int

    @property
    def kind(self):
        return "cnn_flat"

    def arity(self):
        return self.height * self.width * self.channels


@register_config("input.token_sequence")
@dataclasses.dataclass
class TokenSequenceInput:
    """Integer token ids `[batch, timesteps]` drawn below `vocab`: what an
    `EmbeddingSequenceLayer` consumes. Never cast to a float dtype."""

    vocab: int
    timesteps: Optional[int] = None

    @property
    def kind(self):
        return "tokens"

    def arity(self):
        return self.vocab


class InputType:
    """Factory namespace mirroring the reference's static methods."""

    @staticmethod
    def feed_forward(size: int) -> FeedForwardInput:
        return FeedForwardInput(int(size))

    @staticmethod
    def recurrent(size: int, timesteps: Optional[int] = None) -> RecurrentInput:
        return RecurrentInput(int(size), timesteps)

    @staticmethod
    def convolutional(height: int, width: int, channels: int) -> ConvolutionalInput:
        return ConvolutionalInput(int(height), int(width), int(channels))

    @staticmethod
    def convolutional_flat(height: int, width: int, channels: int) -> ConvolutionalFlatInput:
        return ConvolutionalFlatInput(int(height), int(width), int(channels))

    @staticmethod
    def token_sequence(vocab: int, timesteps: Optional[int] = None) -> TokenSequenceInput:
        return TokenSequenceInput(int(vocab), timesteps)

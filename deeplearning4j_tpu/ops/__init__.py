"""Tensor-op surface: activations, losses, conv primitives, Pallas kernels.

This package is the analog of the reference's ND4J op surface (the external
libnd4j engine every layer calls into) re-expressed as jax.numpy / lax /
Pallas functions that XLA fuses into whole-step programs.
"""

from deeplearning4j_tpu.ops.activations import Activation, activation_fn, register_activation
from deeplearning4j_tpu.ops.losses import LossFunction, loss_value, register_loss
from deeplearning4j_tpu.ops.helpers import (
    HelperError,
    get_helper,
    helper_names,
    register_helper,
    set_helper_enabled,
)

# vendor kernels register themselves on import; with one installation a
# kernel module that cannot be imported is a bug, so this raises
from deeplearning4j_tpu.ops import (  # noqa: F401,E402
    pallas_attention,
    pallas_conv_bn,
    pallas_experts,
    pallas_lstm,
    pallas_ssd,
)

"""Functional layer implementations.

Runtime mirror of the config catalog (reference: nn/layers/, 35 files).
Where the reference pairs every layer with a hand-written backpropGradient,
here each layer is a pure forward function and JAX autodiff supplies the
backward pass — the whole network step compiles to one XLA program.

Dispatch: conf dataclass type -> (init_params, forward) via the registry in
registry.py. Param dicts use stable, ordered names so the flattened
parameter view (reference: MultiLayerNetwork flattenedParams,
nn/params/*ParamInitializer layouts) is deterministic.
"""

from deeplearning4j_tpu.nn.layers.registry import (
    forward_layer,
    init_layer_params,
    init_layer_state,
    param_order,
)

# Import impl modules for their registration side effects.
from deeplearning4j_tpu.nn.layers import (  # noqa: E402,F401
    attention, core, conv, experts, norm, rbm, recurrent, special, ssm)

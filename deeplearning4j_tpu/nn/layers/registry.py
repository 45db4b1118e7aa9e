"""Layer implementation registry.

Maps a config dataclass type to its functional implementation:

- init_params(key, conf, dtype) -> dict[name, array]   (trainable)
- init_state(conf, dtype)       -> dict[name, array] | None  (non-trainable,
  e.g. batchnorm running stats — the analog of the reference's layer
  internal state that lives outside the flattened param view)
- forward(conf, params, x, ctx) -> (y, new_state)
- publish(confs, states) -> dict | None   (optional: a kind whose state is
  a set of counters hands them to the metrics registry here; the net calls
  it with host copies where the fit loop already blocks, then zeroes them)

ctx is a LayerContext carrying training flag, rng, masks and minibatch
metadata — the information the reference threads through Layer.activate
arguments and network fields.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional, Tuple, Type

import jax

_INIT: Dict[Type, Callable] = {}
_STATE: Dict[Type, Callable] = {}
_FORWARD: Dict[Type, Callable] = {}
_ORDER: Dict[Type, Callable] = {}
_PUBLISH: Dict[Type, Callable] = {}


@dataclasses.dataclass
class LayerContext:
    """Per-call context for a layer forward."""

    training: bool = False
    rng: Optional[jax.Array] = None
    mask: Optional[jax.Array] = None  # [batch, time] for RNN inputs
    timesteps: Optional[int] = None  # batch time length (for ff<->rnn reshape)
    state: Optional[dict] = None  # layer's mutable state going in
    # the net's PrecisionPolicy compute dtype, for layers that choose the
    # dtype of their matrix products themselves (None: follow the input)
    compute_dtype: Optional[Any] = None
    # a graph vertex's activations beyond the first, for a layer whose
    # conf says `n_inputs() > 1` (the expert layer's router logits)
    extra_inputs: Tuple[Any, ...] = ()


def register_layer(conf_cls, init_fn, forward_fn, order_fn=None, state_fn=None,
                   publish_fn=None):
    _INIT[conf_cls] = init_fn
    _FORWARD[conf_cls] = forward_fn
    if order_fn is not None:
        _ORDER[conf_cls] = order_fn
    if state_fn is not None:
        _STATE[conf_cls] = state_fn
    if publish_fn is not None:
        _PUBLISH[conf_cls] = publish_fn


def _lookup(table, conf):
    for cls in type(conf).__mro__:
        if cls in table:
            return table[cls]
    return None


def init_layer_params(key, conf, dtype) -> Dict[str, Any]:
    fn = _lookup(_INIT, conf)
    if fn is None:
        raise NotImplementedError(f"no init for layer conf {type(conf).__name__}")
    return fn(key, conf, dtype)


def init_layer_state(conf, dtype) -> Optional[dict]:
    fn = _lookup(_STATE, conf)
    return None if fn is None else fn(conf, dtype)


def publish_slots(confs) -> Dict[Callable, list]:
    """Positions in `confs` of the layers whose kind registered a publish
    hook, by hook ({} for a net of kinds that keep no books)."""
    slots: Dict[Callable, list] = {}
    for i, conf in enumerate(confs):
        fn = _lookup(_PUBLISH, conf)
        if fn is not None:
            slots.setdefault(fn, []).append(i)
    return slots


def forward_layer(conf, params, x, ctx: LayerContext) -> Tuple[Any, Optional[dict]]:
    fn = _lookup(_FORWARD, conf)
    if fn is None:
        raise NotImplementedError(f"no forward for layer conf {type(conf).__name__}")
    return fn(conf, params, x, ctx)


def param_order(conf) -> Tuple[str, ...]:
    """Stable parameter-name order used for the flattened view
    (reference: each nn/params/*ParamInitializer defines the layout of its
    slice of flattenedParams)."""
    fn = _lookup(_ORDER, conf)
    if fn is not None:
        return fn(conf)
    return ("W", "b")

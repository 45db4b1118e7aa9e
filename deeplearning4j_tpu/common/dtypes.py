"""Precision policy for TPU execution.

The reference runs f32 (f64 for gradient checks) on CPU/GPU
(GradientCheckUtil.java:77-91 forces global double precision). On TPU the
idiomatic discipline is: bf16 for matmul/conv inputs (MXU-native), f32
accumulation and parameters, f64 only on the CPU backend for numeric
gradient checking. A PrecisionPolicy captures that choice per-model.
"""

from __future__ import annotations

import dataclasses

import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class PrecisionPolicy:
    """Dtype discipline for one network.

    param_dtype:   dtype parameters are stored in (f32 default).
    compute_dtype: dtype activations/matmul operands are cast to
                   (bf16 on TPU for MXU throughput; f32 for parity tests).
    output_dtype:  dtype of network outputs/loss (f32).
    """

    param_dtype: jnp.dtype = jnp.float32
    compute_dtype: jnp.dtype = jnp.float32
    output_dtype: jnp.dtype = jnp.float32

    def cast_input(self, x):
        """Float inputs go to the compute dtype; integer inputs (token ids,
        embedding indices) pass as they are: bf16 holds integers exactly
        only up to 256, so a cast would turn most ids into a neighbour."""
        if x.dtype == self.compute_dtype \
                or not jnp.issubdtype(x.dtype, jnp.floating):
            return x
        return x.astype(self.compute_dtype)

    def cast_output(self, x):
        return x.astype(self.output_dtype) if x.dtype != self.output_dtype else x


_F32 = PrecisionPolicy()
_BF16 = PrecisionPolicy(compute_dtype=jnp.bfloat16)


def default_policy() -> PrecisionPolicy:
    """Full-f32 policy — the safe default; tests and gradient checks use it."""
    return _F32


def tpu_policy() -> PrecisionPolicy:
    """bf16-compute policy — the TPU benchmark configuration."""
    return _BF16


def policy_from_name(name: str) -> PrecisionPolicy:
    name = name.lower()
    if name in ("f32", "float32", "full"):
        return _F32
    if name in ("bf16", "bfloat16", "mixed"):
        return _BF16
    raise ValueError(f"unknown precision policy: {name!r}")

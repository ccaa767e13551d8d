"""The one matmul the Pallas kernels use.

Every kernel here accumulates in fp32 and is checked against fp32 jnp
references (``kernels/ref.py``). On the TPU a dot of fp32 operands at the
default precision runs as a single bf16 pass, which is ~1e-3 away from
those references. So fp32 operands are contracted at ``HIGHEST`` precision,
and bf16 operands keep their single pass, whose products are exact in fp32.
Interpret mode on the CPU computes the same values either way.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def dot(a, b, dimension_numbers=None):
    """fp32-accumulating ``a @ b`` (or a ``lax.dot_general`` with the given
    ``dimension_numbers``). Mixed operand dtypes are promoted to fp32."""
    if a.dtype != b.dtype:
        a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    precision = (jax.lax.Precision.HIGHEST if a.dtype == jnp.float32
                 else None)
    if dimension_numbers is None:
        dimension_numbers = (((a.ndim - 1,), (0,)), ((), ()))
    return jax.lax.dot_general(a, b, dimension_numbers, precision=precision,
                               preferred_element_type=jnp.float32)

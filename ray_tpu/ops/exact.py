"""What the recurrent kernels (`ops.gated_delta`, `ops.ssd`) share: float32
operands multiplied as float32."""
from __future__ import annotations

import jax.numpy as jnp
from jax import lax

F32 = jnp.float32
HIGHEST = lax.Precision.HIGHEST


def dot(a, b, dims=((1,), (0,))):
    """a x b contracted over `dims`, accumulated in float32; operands in
    float32 are multiplied as float32 (the MXU's several passes), not
    rounded to bfloat16."""
    exact = a.dtype == F32 or b.dtype == F32
    if exact:
        a, b = a.astype(F32), b.astype(F32)
    return lax.dot_general(a, b, (dims, ((), ())),
                           preferred_element_type=F32,
                           precision=HIGHEST if exact else None)

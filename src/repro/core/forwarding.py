"""Output forwarding — TM ops applied at producer tile-commit time.

Paper Fig. 5(c): the TPU streams partial output tiles into the TMU before the
full operator finishes, so the next TM op starts early.  On TPU the exact
analogue is applying the TM op's address map inside the *producer kernel's
output BlockSpec index_map*: each matmul tile is written directly to its
TM-transformed destination, so the manipulation is finished the moment the
matmul is — zero extra HBM round-trips and zero added latency.

Two realizations:
  * :func:`matmul_tm` — dispatches to the Pallas ``matmul_tm`` kernel (tile
    commit applies the map) or, as reference, matmul followed by the engine
    inside one jit scope (XLA fuses the gather into the matmul epilogue).
  * :func:`forward_through` — generic producer wrapper for non-matmul ops.
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp

from repro.core.affine import MixedRadixMap
from repro.core.engine import apply_map


def matmul_tm(x: jnp.ndarray, w: jnp.ndarray, m: MixedRadixMap | None,
              *, use_kernel: bool = False, batch_dims: int = 0,
              interpret: bool | None = None) -> jnp.ndarray:
    """``apply_map(m, x @ w)`` with the map folded into the producer.

    ``use_kernel`` selects the Pallas tiled-matmul kernel whose output
    index_map applies ``m`` at tile commit (true output forwarding);
    otherwise XLA fusion of the jnp composition provides the same traffic
    elision at the HLO level.
    """
    if use_kernel and m is not None:
        from repro.kernels.matmul_tm.ops import matmul_tm_call
        return matmul_tm_call(x, w, m, interpret=interpret)
    y = x @ w
    if m is None:
        return y
    return apply_map(m, y, batch_dims=batch_dims)


def forward_through(producer: Callable[..., jnp.ndarray],
                    m: MixedRadixMap, *args, batch_dims: int = 0,
                    **kwargs) -> jnp.ndarray:
    """Compose a TM map onto any producer inside one jit scope."""
    y = producer(*args, **kwargs)
    return apply_map(m, y, batch_dims=batch_dims)

"""Shared typed-projection machinery: {H_T W_T} as ONE grouped matmul.

Every hetero layer in the repo projects per-type row chunks through
per-type weight matrices — ``GroupedLinear`` over node types,
``HeteroConv``'s grouped path over 2·|edge types| neighbor/root groups,
``HGTConv``'s K/Q/V over 3·|node types| groups and its per-type output
heads. They all reduce to the same pack -> grouped GEMM -> unpack
sequence (the CUTLASS grouped-GEMM pattern on the MXU via
``kernels/grouped_matmul``). This module is the single implementation;
the callers contribute only their grouping semantics.

Group sizes are static shape facts (``chunk.shape[0]``) and stay
host-side (``np.int32``) so the packer can make shape decisions under
tracing.
"""

from __future__ import annotations

from typing import List, Optional, Sequence, Union

import jax.numpy as jnp
import numpy as np

from repro.kernels import interpret_default
from repro.kernels.grouped_matmul import ops as gmm_ops


def grouped_apply(chunks: Sequence[jnp.ndarray],
                  weights: Union[jnp.ndarray, Sequence[jnp.ndarray]],
                  biases: Optional[Sequence[Optional[jnp.ndarray]]] = None,
                  *, force_pallas: Optional[bool] = None,
                  interpret: Optional[bool] = None) -> List[jnp.ndarray]:
    """Project ``chunks[g] @ weights[g] (+ biases[g])`` in ONE grouped GEMM.

    ``chunks`` is a list of (n_g, F_in) row blocks, ``weights`` a stacked
    (G, F_in, F_out) tensor (or a list to be stacked — all groups must
    share in/out dims, the grouped-GEMM contract). ``biases`` is an
    optional per-group list; ``None`` entries skip the add. Returns the
    per-group output blocks, unpacked in input order.

    ``interpret=None`` auto-selects interpret mode off-TPU, so callers on
    CPU/GPU exercise the same packed code path the TPU kernel runs.
    """
    sizes = np.asarray([c.shape[0] for c in chunks], np.int32)
    if not isinstance(weights, jnp.ndarray):
        weights = jnp.stack(list(weights))
    if interpret is None:
        interpret = interpret_default()
    out = gmm_ops.grouped_matmul(
        jnp.concatenate(list(chunks), axis=0), weights, sizes,
        force_pallas=force_pallas, interpret=interpret)
    parts: List[jnp.ndarray] = []
    off = 0
    for s in sizes.tolist():
        parts.append(out[off:off + s])
        off += s
    if biases is not None:
        parts = [p if b is None else p + b for p, b in zip(parts, biases)]
    return parts

"""FlashAttention Pallas TPU kernel (online softmax, causal block skipping).

Tiling: grid ``(batch*heads, num_q_blocks, num_kv_blocks)`` with the KV axis
innermost; fp32 accumulator / running-max / running-sum live in VMEM scratch
and persist across the sequential KV steps of one (bh, q) tile — the TPU
rendition of FlashAttention's SRAM accumulators. Causal tiles strictly above
the diagonal are skipped via ``pl.when`` (no MXU work issued).

Block sizes default to (128, 128): MXU-native, and a (128 q x 128 kv) logits
tile + two (128, d) operand tiles fit comfortably in ~16 MB VMEM for d<=256.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BQ = 128
DEFAULT_BKV = 128
NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, out_ref, acc_ref, m_ref, l_ref, *,
                  scale: float, causal: bool, n_kv: int, block_q: int,
                  block_kv: int, seq_kv: int, q_offset: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # Causal skip: the whole KV tile is in the future of the whole Q tile.
    first_q = qi * block_q + q_offset
    run = True
    if causal:
        run = ki * block_kv <= first_q + block_q - 1

    @pl.when(run)
    def _compute():
        q = q_ref[0].astype(jnp.float32) * scale          # (BQ, D)
        k = k_ref[0].astype(jnp.float32)                  # (BKV, D)
        v = v_ref[0].astype(jnp.float32)                  # (BKV, D)
        logits = jnp.dot(q, k.T, preferred_element_type=jnp.float32)
        kpos = ki * block_kv + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_kv), 1)
        qpos = q_offset + qi * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_kv), 0)
        mask = kpos < seq_kv
        if causal:
            mask = mask & (qpos >= kpos)
        logits = jnp.where(mask, logits, NEG_INF)

        m_prev = m_ref[...]
        blk_max = jnp.max(logits, axis=-1, keepdims=True)  # (BQ, 1)
        m_new = jnp.maximum(m_prev, blk_max)
        p = jnp.exp(logits - m_new)
        p = jnp.where(mask, p, 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + p.sum(axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jnp.dot(
            p, v, preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(ki == n_kv - 1)
    def _flush():
        out_ref[0] = (acc_ref[...] /
                      jnp.maximum(l_ref[...], 1e-20)).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "causal", "block_q", "block_kv", "q_offset", "interpret"))
def _flash_attention_pallas_impl(q: jnp.ndarray, k: jnp.ndarray,
                                 v: jnp.ndarray, *, causal: bool = True,
                                 block_q: int = DEFAULT_BQ,
                                 block_kv: int = DEFAULT_BKV,
                                 q_offset: int = 0,
                                 interpret: bool = False) -> jnp.ndarray:
    """q: (B, Sq, H, D), k/v: (B, Sk, H, D) (pre-broadcast GQA upstream).

    Sq % block_q == 0 and Sk % block_kv == 0 (pad upstream; padded KV masked
    via ``seq_kv``).
    """
    b, sq, h, d = q.shape
    sk = k.shape[1]
    assert sq % block_q == 0 and sk % block_kv == 0
    n_q, n_kv = sq // block_q, sk // block_kv
    scale = 1.0 / (d ** 0.5)

    # Fold batch & heads into the leading grid dim; move seq to dim 1.
    qr = q.transpose(0, 2, 1, 3).reshape(b * h, sq, d)
    kr = k.transpose(0, 2, 1, 3).reshape(b * h, sk, d)
    vr = v.transpose(0, 2, 1, 3).reshape(b * h, sk, d)

    kernel = functools.partial(
        _flash_kernel, scale=scale, causal=causal, n_kv=n_kv,
        block_q=block_q, block_kv=block_kv, seq_kv=sk, q_offset=q_offset)

    out = pl.pallas_call(
        kernel,
        grid=(b * h, n_q, n_kv),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, block_kv, d), lambda bh, qi, ki: (bh, ki, 0)),
            pl.BlockSpec((1, block_kv, d), lambda bh, qi, ki: (bh, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda bh, qi, ki: (bh, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, sq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
        ],
        interpret=interpret,
        name="_flash_kernel",
    )(qr, kr, vr)
    return out.reshape(b, h, sq, d).transpose(0, 2, 1, 3)


from repro.kernels import forward_only_pallas

_flash_attention_pallas_cv = forward_only_pallas(
    lambda causal, block_q, block_kv, q_offset, interpret, q, k, v:
        _flash_attention_pallas_impl(q, k, v, causal=causal,
                                     block_q=block_q, block_kv=block_kv,
                                     q_offset=q_offset, interpret=interpret),
    num_static=5,
    message=(
        "flash_attention_pallas is the raw Pallas kernel and has no "
        "backward rule. Differentiate through "
        "repro.kernels.flash_attention.ops.attention with "
        "REPRO_USE_PALLAS=0 (the chunked XLA path is differentiable); the "
        "LM train path keeps XLA attention."))


def flash_attention_pallas(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                           causal: bool = True, block_q: int = DEFAULT_BQ,
                           block_kv: int = DEFAULT_BKV, q_offset: int = 0,
                           interpret: bool = False) -> jnp.ndarray:
    """FlashAttention Pallas kernel (see :func:`_flash_attention_pallas_impl`).

    Forward-only: differentiating this raw entry point raises a clear
    ``NotImplementedError`` naming the differentiable XLA path and the
    ``REPRO_USE_PALLAS`` fallback env var, instead of an opaque
    ``pallas_call`` trace error.
    """
    return _flash_attention_pallas_cv(causal, block_q, block_kv, q_offset,
                                      interpret, q, k, v)

"""Flash attention Pallas kernels (forward + single-token decode).

The perf-critical compute hot-spot of every LM-family architecture in the
pool.  TM-layer relevance: the online-softmax accumulator is the *evaluate*
scheme of the RME generalized to running max/sum, and the KV-block streaming
is coarse-grained TM (block Route) — attention is where TM ops and MXU
compute meet, which is why the paper benchmarks a Transformer (Table IV).

Forward: grid (batch·heads, q_blocks, kv_blocks); kv innermost, carrying
running (m, l, acc) in VMEM scratch; causal masking by block skip + in-block
iota mask.  Decode: one query token vs a long KV cache, grid over kv blocks.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.platform import pallas_interpret

NEG_INF = -1e30


def _fa_fwd_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                   scale: float, causal: bool, bq: int, bk: int, nk: int):
    kb = pl.program_id(2)
    qb = pl.program_id(1)

    @pl.when(kb == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    run = (not causal) or (kb * bk <= qb * bq + bq - 1)

    @pl.when(run)
    def _block():
        q = q_ref[0]                      # (bq, d)
        k = k_ref[0]                      # (bk, d)
        v = v_ref[0]
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
        if causal:
            qi = qb * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            ki = kb * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            s = jnp.where(qi >= ki, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1)
        acc_ref[...] = acc_ref[...] * alpha[:, None] + jnp.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(kb == nk - 1)
    def _commit():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                    causal: bool = True, scale: float | None = None,
                    bq: int = 128, bk: int = 128,
                    interpret: bool | None = None) -> jnp.ndarray:
    """q, k, v: (BH, S, D) -> (BH, S, D).  GQA repeat handled by caller."""
    BH, S, D = q.shape
    Sk = k.shape[1]
    if scale is None:
        scale = 1.0 / math.sqrt(D)
    bq = math.gcd(S, bq)
    bk = math.gcd(Sk, bk)
    nq, nk = S // bq, Sk // bk
    kern = functools.partial(_fa_fwd_kernel, scale=scale, causal=causal,
                             bq=bq, bk=bk, nk=nk)
    return pl.pallas_call(
        kern,
        grid=(BH, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, D), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bk, D), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, S, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq, D), jnp.float32),
        ],
        name="flash_attention",
        interpret=(pallas_interpret(q) if interpret is None
                   else interpret),
    )(q, k, v)


# ---------------------------------------------------------------------------
# decode: one new token against a KV cache (paper shape decode_32k/long_500k)
# ---------------------------------------------------------------------------

def _fa_decode_kernel(q_ref, k_ref, v_ref, len_ref, o_ref, m_ref, l_ref,
                      acc_ref, *, scale: float, bk: int, nk: int):
    kb = pl.program_id(1)

    @pl.when(kb == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0]                          # (1, d)
    k = k_ref[0]                          # (bk, d)
    v = v_ref[0]
    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale  # (1, bk)
    pos = kb * bk + jax.lax.broadcasted_iota(jnp.int32, (1, bk), 1)
    s = jnp.where(pos < len_ref[0], s, NEG_INF)
    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
    p = jnp.exp(s - m_new[:, None])
    alpha = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1)
    acc_ref[...] = acc_ref[...] * alpha[:, None] + jnp.dot(
        p.astype(v.dtype), v, preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(kb == nk - 1)
    def _commit():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


def flash_decode(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                 length: jnp.ndarray, *, scale: float | None = None,
                 bk: int = 512, interpret: bool | None = None) -> jnp.ndarray:
    """q: (BH, 1, D); k/v: (BH, S, D); length: () valid cache length."""
    BH, S, D = k.shape
    if scale is None:
        scale = 1.0 / math.sqrt(D)
    bk = math.gcd(S, bk)
    nk = S // bk
    kern = functools.partial(_fa_decode_kernel, scale=scale, bk=bk, nk=nk)
    lens = jnp.asarray(length, dtype=jnp.int32).reshape(1)
    return pl.pallas_call(
        kern,
        grid=(BH, nk),
        in_specs=[
            pl.BlockSpec((1, 1, D), lambda b, j: (b, 0, 0)),
            pl.BlockSpec((1, bk, D), lambda b, j: (b, j, 0)),
            pl.BlockSpec((1, bk, D), lambda b, j: (b, j, 0)),
            pl.BlockSpec((1,), lambda b, j: (0,)),
        ],
        out_specs=pl.BlockSpec((1, 1, D), lambda b, j: (b, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, 1, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((1,), jnp.float32),
            pltpu.VMEM((1,), jnp.float32),
            pltpu.VMEM((1, D), jnp.float32),
        ],
        name="flash_attention",
        interpret=(pallas_interpret(q) if interpret is None
                   else interpret),
    )(q, k, v, lens)

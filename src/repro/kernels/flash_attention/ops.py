from functools import partial

import jax

from repro.kernels.flash_attention.flash_attention import (flash_attention,
                                                           flash_decode)


@partial(jax.jit, static_argnames=("causal", "interpret", "bq", "bk"))
def flash_attention_call(q, k, v, *, causal=True, bq=128, bk=128,
                         interpret=None):
    return flash_attention(q, k, v, causal=causal, bq=bq, bk=bk,
                           interpret=interpret)


@partial(jax.jit, static_argnames=("interpret", "bk"))
def flash_decode_call(q, k, v, length, *, bk=512, interpret=None):
    return flash_decode(q, k, v, length, bk=bk, interpret=interpret)

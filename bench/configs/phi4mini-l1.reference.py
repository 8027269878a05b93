"""Plain reference of ``phi4mini-l1``: one decoder layer and the tied
unembedding, in float32 at ``highest`` matmul precision.

It imports nothing of the program.  It follows the configuration file as it
is run (full rotary, RMSNorm eps from the file, no rope scaling) and takes
the benchmark's own weights, upcast to float32.  ``quantized=True`` is the
control: every matmul operand rounded to float8 e4m3 with a per-tensor
scale, the precision step below the configuration's bfloat16.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
_E4M3_MAX = 448.0


def _q(x, quantized: bool):
    if not quantized:
        return x
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / _E4M3_MAX
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(eq: str, a, b, quantized: bool):
    return jnp.einsum(eq, _q(a, quantized), _q(b, quantized),
                      precision=HIGHEST)


def _rmsnorm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def _rope(x, theta):
    """Rotary embedding on the two halves of the head dimension, positions
    0..T-1.  x: (B, T, heads, hd)."""
    T, hd = x.shape[1], x.shape[3]
    inv = 1.0 / theta ** (np.arange(0, hd, 2, dtype=np.float64) / hd)
    ang = np.arange(T, dtype=np.float64)[:, None] * inv[None, :]
    cos = jnp.asarray(np.cos(ang), jnp.float32)[None, :, None, :]
    sin = jnp.asarray(np.sin(ang), jnp.float32)[None, :, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


@functools.partial(jax.jit, static_argnames=("sizes", "lo", "hi",
                                              "quantized"))
def _logits(w, tokens, *, sizes, lo, hi, quantized):
    D, H, KV, hd, F, theta, eps = sizes
    f32 = lambda a: a.astype(jnp.float32)  # noqa: E731
    E = f32(w["embed"]["e"])
    blk = jax.tree_util.tree_map(lambda a: f32(a[0]), w["blocks"])
    B, T = tokens.shape
    x = E[tokens]
    h = _rmsnorm(x, blk["ln1"]["g"], eps)
    qkv = _mm("btd,de->bte", h, blk["attn"]["wqkv"], quantized)
    q = qkv[..., :H * hd].reshape(B, T, H, hd)
    k = qkv[..., H * hd:(H + KV) * hd].reshape(B, T, KV, hd)
    v = qkv[..., (H + KV) * hd:].reshape(B, T, KV, hd)
    q, k = _rope(q, theta), _rope(k, theta)
    G = H // KV
    qg = q.reshape(B, T, KV, G, hd)
    s = _mm("bskgd,btkd->bkgst", qg, k, quantized) / np.sqrt(hd)
    causal = np.tril(np.ones((T, T), bool))
    s = jnp.where(causal, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = _mm("bkgst,btkd->bskgd", p, v, quantized).reshape(B, T, H * hd)
    x = x + _mm("btd,de->bte", o, blk["attn"]["wo"], quantized)
    h = _rmsnorm(x, blk["ln2"]["g"], eps)
    up = _mm("btd,de->bte", h, blk["mlp"]["wi"], quantized)
    m = jax.nn.silu(up[..., :F]) * up[..., F:]
    x = x + _mm("btf,fd->btd", m, blk["mlp"]["wo"], quantized)
    x = _rmsnorm(x[:, lo:hi], f32(w["final_norm"]["g"]), eps)
    return _mm("btd,vd->btv", x, E, quantized)


def logits(w, spec: dict, tokens: np.ndarray, lo: int, hi: int, *,
           quantized: bool = False, block: int = 4) -> np.ndarray:
    """Logits (B, hi - lo, V) float32 at positions ``[lo, hi)`` of
    ``tokens`` (B, T), computed ``block`` sequences at a time."""
    sizes = (spec["hidden_size"], spec["num_attention_heads"],
             spec["num_key_value_heads"], spec["head_dim"],
             spec["intermediate_size"], float(spec["rope_theta"]),
             float(spec["rms_norm_eps"]))
    out = []
    for i in range(0, len(tokens), block):
        part = tokens[i:i + block]
        n = len(part)
        if n < block:  # one compiled shape: pad with copies
            part = np.concatenate([part, np.repeat(part[-1:], block - n, 0)])
        out.append(np.asarray(_logits(w, jnp.asarray(part), sizes=sizes,
                                      lo=lo, hi=hi,
                                      quantized=quantized))[:n])
    return np.concatenate(out)


def rms_err_sigma(got: np.ndarray, ref: np.ndarray) -> np.ndarray:
    """Per row: the RMS of ``got - ref`` in standard deviations of the
    reference row.  got, ref: (..., V)."""
    d = got.astype(np.float64) - ref
    return np.sqrt((d * d).mean(-1)) / ref.std(-1)

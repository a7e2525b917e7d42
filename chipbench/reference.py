"""The plain reference: the configuration's forward pass in float32
``jax.numpy`` at the highest matmul precision, with no kernels, no cache
and no batching, over document + question + answer at once.

It follows the published Qwen2 / Qwen3 decoder (RMSNorm, rotary position
embedding with half rotation, grouped-query causal attention, SwiGLU,
tied embeddings), with the departures the configuration file lists: the
RMSNorm gain is stored as ``1 + w``, and Qwen3's per-head q/k norm is
absent as it is in the program. It imports nothing of the program and
reads only the weights the benchmark drew.

``fp8=True`` is the control: the same forward with every weight matmul
(q, k, v, o, gate, up, down and the output head) taking float8 e4m3
inputs, weights scaled per output channel and activations per token, the
step below the configuration's bfloat16 that would tempt a later change.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
F8_MAX = 448.0          # largest finite float8_e4m3fn


def _f8(t, axis):
    """Round ``t`` to float8 e4m3 with one scale per slice along
    ``axis`` (the contracting axis), back in float32."""
    amax = jnp.max(jnp.abs(t), axis=axis, keepdims=True)
    s = jnp.where(amax > 0, amax / F8_MAX, 1.0)
    return (t / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(eq, x, w, fp8, w_axis):
    if fp8:
        x = _f8(x, -1)
        w = _f8(w, w_axis)
    return jnp.einsum(eq, x, w, precision=HIGHEST)


def _rms(x, w, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + w)


def _rope(x, theta):
    """x: (s, h, d), positions 0..s-1."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def make(conf: dict, *, lo: int, n: int, fp8: bool = False):
    """A jitted ``fn(params, tokens) -> (n, vocab) float32 logits`` of the
    positions ``lo .. lo + n - 1``; ``tokens`` is ``(s,)`` int32."""
    eps = float(conf["rms_norm_eps"])
    theta = float(conf["rope_theta"])
    hq, hkv = conf["num_attention_heads"], conf["num_key_value_heads"]
    hd, vocab = conf["head_dim"], conf["vocab_size"]
    g = hq // hkv
    bias = conf["attention_bias"]

    def layer(x, bp):
        bp = jax.tree.map(lambda a: a.astype(jnp.float32), bp)
        at, s = bp["attn"], x.shape[0]
        h = _rms(x, bp["attn_norm"]["scale"], eps)
        q = _mm("sd,dhk->shk", h, at["wq"], fp8, 0)
        k = _mm("sd,dhk->shk", h, at["wk"], fp8, 0)
        v = _mm("sd,dhk->shk", h, at["wv"], fp8, 0)
        if bias:
            q, k, v = q + at["bq"], k + at["bk"], v + at["bv"]
        q, k = _rope(q, theta), _rope(k, theta)
        q = q.reshape(s, hkv, g, hd)
        sc = jnp.einsum("qhgd,khd->hgqk", q, k, precision=HIGHEST) \
            * hd ** -0.5
        causal = jnp.tril(jnp.ones((s, s), bool))
        p = jax.nn.softmax(jnp.where(causal, sc, -jnp.inf), axis=-1)
        o = jnp.einsum("hgqk,khd->qhgd", p, v, precision=HIGHEST)
        o = o.reshape(s, hq, hd)
        x = x + _mm("shk,hkd->sd", o, at["wo"], fp8, (0, 1))
        h = _rms(x, bp["mlp_norm"]["scale"], eps)
        ml = bp["mlp"]
        gate = _mm("sd,df->sf", h, ml["w_gate"], fp8, 0)
        up = _mm("sd,df->sf", h, ml["w_up"], fp8, 0)
        x = x + _mm("sf,fd->sd", jax.nn.silu(gate) * up, ml["w_down"],
                    fp8, 0)
        return x, None

    @jax.jit
    def fn(params, tokens):
        emb = params["emb"].astype(jnp.float32)
        x = emb[tokens]
        x, _ = jax.lax.scan(layer, x, params["blocks"])
        x = _rms(x[lo:lo + n], params["final_norm"]["scale"].astype(
            jnp.float32), eps)
        head = emb if "unemb" not in params else \
            params["unemb"].astype(jnp.float32).T
        return _mm("sd,vd->sv", x, head[:vocab], fp8, 1)

    return fn

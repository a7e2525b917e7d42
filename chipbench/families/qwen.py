"""The Qwen2 / Qwen3 dense decoder family, the family of a configuration
file without a ``"family"`` key.

The program's parameter tree and the weights' scales, the program's
model at the file's sizes, the plain reference with its float8 control,
and the operations one token needs.

The reference is the configuration's forward pass in float32
``jax.numpy`` at the highest matmul precision, with no kernels, no cache
and no batching, over document + question + answer at once. It follows
the published Qwen2 / Qwen3 decoder (RMSNorm, rotary position embedding
with half rotation, grouped-query causal attention, SwiGLU, tied
embeddings), with the departures the configuration file lists: the
RMSNorm gain is stored as ``1 + w``, and Qwen3's per-head q/k norm is
absent as it is in the program. It imports nothing of the program and
reads only the weights the benchmark drew.

``fp8=True`` is the control: the same forward with every weight matmul
(q, k, v, o, gate, up, down and the output head) taking float8 e4m3
inputs, weights scaled per output channel and activations per token, the
step below the configuration's bfloat16 that would tempt a later change.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

VECTOR_STD = 0.05       # norm-weight offsets and biases
HIGHEST = jax.lax.Precision.HIGHEST
F8_MAX = 448.0          # largest finite float8_e4m3fn


def shapes(conf: dict) -> dict:
    """Leaf shapes of the program's parameter tree, from the published
    sizes in a configuration file."""
    n, d = conf["num_hidden_layers"], conf["hidden_size"]
    hq, hkv = conf["num_attention_heads"], conf["num_key_value_heads"]
    hd, f = conf["head_dim"], conf["intermediate_size"]
    v = -(-conf["vocab_size"] // 128) * 128        # the program pads to /128
    attn = {"wq": (n, d, hq, hd), "wk": (n, d, hkv, hd),
            "wv": (n, d, hkv, hd), "wo": (n, hq, hd, d)}
    if conf["attention_bias"]:
        attn.update(bq=(n, hq, hd), bk=(n, hkv, hd), bv=(n, hkv, hd))
    tree = {"emb": (v, d), "final_norm": {"scale": (d,)},
            "blocks": {"attn_norm": {"scale": (n, d)},
                       "mlp_norm": {"scale": (n, d)}, "attn": attn,
                       "mlp": {"w_up": (n, d, f), "w_gate": (n, d, f),
                               "w_down": (n, f, d)}}}
    if not conf["tie_word_embeddings"]:
        tree["unemb"] = (d, v)
    return tree


def init_std(leaf: str, shape: tuple, conf: dict) -> float:
    """The standard deviation of the leaf named ``leaf``."""
    if leaf in ("scale", "bq", "bk", "bv"):
        return VECTOR_STD                      # norms and biases
    if leaf in ("emb", "unemb"):
        fan_in = conf["hidden_size"]
    elif leaf == "wo":
        fan_in = shape[1] * shape[2]           # (layers, heads, hd, d)
    else:
        fan_in = shape[1]                      # (layers, in, ...)
    std = fan_in ** -0.5
    if leaf in ("wo", "w_down"):
        std /= np.sqrt(2 * conf["num_hidden_layers"])
    return std


def program_config(conf: dict):
    """The program's ``ModelConfig`` at the configuration file's sizes."""
    from repro.configs import get_config

    return dataclasses.replace(
        get_config(conf["program_arch"]), name=conf["name"],
        num_layers=conf["num_hidden_layers"], d_model=conf["hidden_size"],
        num_heads=conf["num_attention_heads"],
        num_kv_heads=conf["num_key_value_heads"], head_dim=conf["head_dim"],
        d_ff=conf["intermediate_size"], vocab_size=conf["vocab_size"],
        qkv_bias=conf["attention_bias"], rope_theta=float(conf["rope_theta"]),
        tie_embeddings=conf["tie_word_embeddings"], activation="swiglu",
        norm="rmsnorm", moe=None)


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


def make_reference(conf: dict, *, lo: int, n: int, fp8: bool):
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


def token_forward_flops(conf: dict, keys: int) -> float:
    """One token's forward through every layer and the output head, its
    attention reading ``keys`` cached positions (itself included)."""
    d, f = conf["hidden_size"], conf["intermediate_size"]
    hq, hkv = conf["num_attention_heads"], conf["num_key_value_heads"]
    hd = conf["head_dim"]
    proj = 2 * d * (hq + 2 * hkv) * hd + 2 * hq * hd * d
    attn = 2 * 2 * hq * hd * keys
    mlp = 2 * 3 * d * f
    return conf["num_hidden_layers"] * (proj + attn + mlp) \
        + 2 * d * conf["vocab_size"]


def token_params(conf: dict) -> int:
    """Parameters one token reads: every leaf of the tree, the (tied)
    embedding once."""
    return sum(int(np.prod(s)) for s in jax.tree.leaves(
        shapes(conf), is_leaf=lambda x: isinstance(x, tuple)))


def kv_plane_values(conf: dict, chunk_tokens: int) -> int:
    """Values in one streamed K or V plane: a chunk of one layer."""
    return chunk_tokens * conf["num_key_value_heads"] * conf["head_dim"]

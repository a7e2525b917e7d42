"""A model family for the tests only: sparse experts of which this chip
holds a share, per-head q/k RMSNorm, and sliding-window layers mixed with
full ones by ``layer_types``. The program has no model for it.

Each layer: RMSNorm, q/k/v projections, an RMSNorm over each head of q
and of k, rotary embedding, grouped-query attention (causal, and on a
``sliding_attention`` layer only the last ``sliding_window`` positions),
the output projection; then RMSNorm and the expert layer. The router
scores all ``num_experts``, keeps the ``num_experts_per_tok`` best and
softmaxes their scores; the chip holds the first ``num_local_experts``
and adds only their part of the result, as one chip of an
expert-parallel deployment does. Every norm's gain is ``1 + w``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


def shapes(conf: dict) -> dict:
    n, d = conf["num_hidden_layers"], conf["hidden_size"]
    hq, hkv = conf["num_attention_heads"], conf["num_key_value_heads"]
    hd, f = conf["head_dim"], conf["moe_intermediate_size"]
    e, el = conf["num_experts"], conf["num_local_experts"]
    v = conf["vocab_size"]
    return {"emb": (v, d), "unemb": (d, v), "final_norm": {"scale": (d,)},
            "blocks": {
                "attn_norm": {"scale": (n, d)},
                "mlp_norm": {"scale": (n, d)},
                "attn": {"wq": (n, d, hq, hd), "wk": (n, d, hkv, hd),
                         "wv": (n, d, hkv, hd), "wo": (n, hq, hd, d),
                         "q_norm": (n, hd), "k_norm": (n, hd)},
                "moe": {"router": (n, d, e), "w_gate": (n, el, d, f),
                        "w_up": (n, el, d, f), "w_down": (n, el, f, d)}}}


def init_std(leaf: str, shape: tuple, conf: dict) -> float:
    if leaf in ("scale", "q_norm", "k_norm"):
        return 0.05
    if leaf in ("emb", "unemb"):
        return conf["hidden_size"] ** -0.5
    if leaf == "wo":
        return (shape[1] * shape[2]) ** -0.5
    return shape[-2] ** -0.5               # (..., in, out)


def program_config(conf: dict):
    return None


def _rms(x, w, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + w)


def _rope(x, theta):
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def make_reference(conf: dict, *, lo: int, n: int, fp8: bool):
    if fp8:
        raise NotImplementedError("the test family has no float8 control")
    eps, theta = conf["rms_norm_eps"], conf["rope_theta"]
    hq, hkv, hd = (conf["num_attention_heads"], conf["num_key_value_heads"],
                   conf["head_dim"])
    k_top, el = conf["num_experts_per_tok"], conf["num_local_experts"]
    big = np.iinfo(np.int32).max
    windows = jnp.asarray([conf["sliding_window"] if t == "sliding_attention"
                           else big for t in conf["layer_types"]], jnp.int32)

    def layer(x, inputs):
        bp, window = inputs
        bp = jax.tree.map(lambda a: a.astype(jnp.float32), bp)
        at, s = bp["attn"], x.shape[0]
        h = _rms(x, bp["attn_norm"]["scale"], eps)
        q = jnp.einsum("sd,dhk->shk", h, at["wq"], precision=HIGHEST)
        k = jnp.einsum("sd,dhk->shk", h, at["wk"], precision=HIGHEST)
        v = jnp.einsum("sd,dhk->shk", h, at["wv"], precision=HIGHEST)
        q = _rope(_rms(q, at["q_norm"], eps), theta)
        k = _rope(_rms(k, at["k_norm"], eps), theta)
        k = jnp.repeat(k, hq // hkv, axis=1)
        v = jnp.repeat(v, hq // hkv, axis=1)
        sc = jnp.einsum("qhd,khd->hqk", q, k, precision=HIGHEST) * hd ** -0.5
        pos = jnp.arange(s)
        seen = (pos[None, :] <= pos[:, None]) & \
            (pos[:, None] - pos[None, :] < window)
        p = jax.nn.softmax(jnp.where(seen, sc, -jnp.inf), axis=-1)
        o = jnp.einsum("hqk,khd->qhd", p, v, precision=HIGHEST)
        x = x + jnp.einsum("shk,hkd->sd", o, at["wo"], precision=HIGHEST)

        h = _rms(x, bp["mlp_norm"]["scale"], eps)
        mo = bp["moe"]
        scores = jnp.einsum("sd,de->se", h, mo["router"], precision=HIGHEST)
        top, idx = jax.lax.top_k(scores, k_top)
        gates = jax.nn.softmax(top, axis=-1)
        # each local expert's gate per token: 0 where it was not chosen
        local = (idx[:, :, None] == jnp.arange(el)).astype(jnp.float32)
        weight = jnp.einsum("sk,ske->se", gates, local)
        gate = jnp.einsum("sd,edf->sef", h, mo["w_gate"], precision=HIGHEST)
        up = jnp.einsum("sd,edf->sef", h, mo["w_up"], precision=HIGHEST)
        out = jnp.einsum("sef,efd->sed", jax.nn.silu(gate) * up,
                         mo["w_down"], precision=HIGHEST)
        return x + jnp.einsum("se,sed->sd", weight, out), None

    @jax.jit
    def fn(params, tokens):
        x = params["emb"].astype(jnp.float32)[tokens]
        x, _ = jax.lax.scan(layer, x, (params["blocks"], windows))
        x = _rms(x[lo:lo + n], params["final_norm"]["scale"].astype(
            jnp.float32), eps)
        return jnp.einsum("sd,dv->sv", x, params["unemb"].astype(
            jnp.float32), precision=HIGHEST)

    return fn


def token_params(conf: dict) -> float:
    """Matrix parameters one token reads: attention, the router, the
    output head, and the experts it is routed to among those held here
    (``num_experts_per_tok`` of ``num_experts``, this chip's share of
    them on average)."""
    d, hd = conf["hidden_size"], conf["head_dim"]
    hq, hkv = conf["num_attention_heads"], conf["num_key_value_heads"]
    active = conf["num_experts_per_tok"] * conf["num_local_experts"] \
        / conf["num_experts"]
    layer = d * (2 * hq + 2 * hkv) * hd + d * conf["num_experts"] \
        + active * 3 * d * conf["moe_intermediate_size"]
    return conf["num_hidden_layers"] * layer + d * conf["vocab_size"]


def token_forward_flops(conf: dict, keys: int) -> float:
    attn = sum(2 * 2 * conf["num_attention_heads"] * conf["head_dim"]
               * (min(keys, conf["sliding_window"])
                  if t == "sliding_attention" else keys)
               for t in conf["layer_types"])
    return 2 * token_params(conf) + attn


def kv_plane_values(conf: dict, chunk_tokens: int) -> int:
    return chunk_tokens * conf["num_key_value_heads"] * conf["head_dim"]

"""Shared model building blocks (pure JAX, GSPMD-friendly).

Conventions:
  - parameters are declared as ParamDef (shape + logical axes + init) trees;
  - activations are bf16 unless noted; softmax/statistics in fp32;
  - the reference attention is a chunked flash implementation (lax.scan over
    KV blocks with running softmax) so 32k-token prefill never materializes
    an S x S score matrix. The Pallas kernels in repro.kernels are the TPU
    hot path; this file is the oracle + dry-run path.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.distributed.sharding import constrain

# ----------------------------------------------------------------------------
# Parameter declaration
# ----------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ParamDef:
    shape: tuple
    axes: tuple          # logical axis names, len == len(shape)
    init: str = "normal"  # normal | zeros | ones
    scale: float = 0.02
    dtype: str = "bfloat16"

    def __post_init__(self):
        assert len(self.shape) == len(self.axes), (self.shape, self.axes)


def is_def(x) -> bool:
    return isinstance(x, ParamDef)


def abstract_params(defs):
    return jax.tree.map(
        lambda d: jax.ShapeDtypeStruct(d.shape, jnp.dtype(d.dtype)),
        defs, is_leaf=is_def)


def axes_tree(defs):
    return jax.tree.map(lambda d: d.axes, defs, is_leaf=is_def)


def init_params(defs, rng):
    flat, treedef = jax.tree.flatten(defs, is_leaf=is_def)
    keys = jax.random.split(rng, len(flat))
    out = []
    for d, k in zip(flat, keys):
        dt = jnp.dtype(d.dtype)
        if d.init == "zeros":
            out.append(jnp.zeros(d.shape, dt))
        elif d.init == "ones":
            out.append(jnp.ones(d.shape, dt))
        elif d.init == "normal":
            out.append(_normal_leaf(k, d.shape, d.scale, dt))
        else:
            raise ValueError(d.init)
    return jax.tree.unflatten(treedef, out)


@partial(jax.jit, static_argnames=("shape", "scale", "dtype"))
def _normal_leaf(key, shape, scale, dtype):
    # jitted so the fp32 draw fuses into the cast: a bf16 leaf is never
    # held in fp32 (the stacked MLP leaf of a 4B model would add 3.6 GB)
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


def pspec_tree(defs, rules):
    return jax.tree.map(lambda d: rules.spec(d.axes, d.shape),
                        defs, is_leaf=is_def)


def sharding_tree(defs, rules):
    return jax.tree.map(lambda d: rules.sharding(d.axes, d.shape),
                        defs, is_leaf=is_def)


# ----------------------------------------------------------------------------
# Norms / activations / RoPE
# ----------------------------------------------------------------------------


def rms_norm(x, scale, eps: float = 1e-6):
    """Statistics in fp32, application in x.dtype. Upcasting the whole
    tensor (flax-style) makes the backward residual-stream cotangent fp32
    at the exact point GSPMD inserts the model-axis combine — measured 2x
    collective bytes on chameleon-34b train_4k (EXPERIMENTS.md §Perf)."""
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1,
                   keepdims=True)
    inv = jax.lax.rsqrt(var + eps).astype(x.dtype)
    return x * inv * (1.0 + scale.astype(x.dtype))


def layer_norm(x, scale, bias, eps: float = 1e-5):
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mu), axis=-1, keepdims=True)
    inv = jax.lax.rsqrt(var + eps).astype(x.dtype)
    mu = mu.astype(x.dtype)
    return (x - mu) * inv * (1.0 + scale.astype(x.dtype)) \
        + bias.astype(x.dtype)


def apply_norm(cfg, x, p):
    if cfg.norm == "rmsnorm":
        return rms_norm(x, p["scale"])
    return layer_norm(x, p["scale"], p["bias"])


def norm_defs(cfg, d: int, prefix_shape=()) -> dict:
    defs = {"scale": ParamDef(prefix_shape + (d,),
                              ("layers",) * len(prefix_shape) + ("embed",),
                              init="zeros")}
    if cfg.norm == "layernorm":
        defs["bias"] = ParamDef(prefix_shape + (d,),
                                ("layers",) * len(prefix_shape) + ("embed",),
                                init="zeros")
    return defs


@jax.custom_vjp
def bf16_grad_barrier(x):
    """Identity forward; casts the cotangent to bf16.

    The training residual stream is bf16, but a single fp32 cotangent
    entering it (e.g. from an fp32 loss head) stays fp32 through every
    residual add below (bf16 + f32 promotes), making every model-axis
    backward collective fp32 — 2x wire. This barrier pins the gradient
    dtype at block boundaries (§Perf iteration 4c)."""
    return x


def _bgb_fwd(x):
    return x, None


def _bgb_bwd(_, g):
    return (g.astype(jnp.bfloat16),)


bf16_grad_barrier.defvjp(_bgb_fwd, _bgb_bwd)


def act_fn(name: str):
    if name in ("swiglu", "silu"):
        return jax.nn.silu
    if name in ("geglu", "gelu"):
        return partial(jax.nn.gelu, approximate=True)
    raise ValueError(name)


def rope(x, positions, theta: float):
    """Rotary embedding, llama-style half rotation.

    x: (..., s, h, d); positions: broadcastable to (..., s).
    """
    d = x.shape[-1]
    half = d // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[..., None] * freqs   # (..., s, half)
    cos = jnp.cos(ang)[..., None, :]                         # (..., s, 1, half)
    sin = jnp.sin(ang)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ----------------------------------------------------------------------------
# Chunked flash attention (reference / dry-run path)
# ----------------------------------------------------------------------------


def flash_attention(q, k, v, *, causal: bool, q_offset=0,
                    kv_len: Optional[jax.Array] = None,
                    kv_chunk: int = 1024, scale: Optional[float] = None,
                    return_stats: bool = False):
    """Memory-efficient attention with GQA support.

    q: (b, sq, hq, d); k/v: (b, skv, hkv, d), hq % hkv == 0.
    kv_len: optional dynamic valid length (decode); default skv.
    Returns (b, sq, hq, d) in q.dtype.

    GQA is handled by *repeating* kv heads to hq (Megatron convention)
    rather than a (hkv, g) reshape of q — a grouped reshape of a
    model-axis-sharded head dim is not a rectangular resharding and would
    force GSPMD to all-gather the q heads.
    """
    b, sq, hq, d = q.shape
    _, skv, hkv, _ = k.shape
    g = hq // hkv
    scale = scale if scale is not None else d ** -0.5
    if g > 1:
        k = jnp.repeat(k, g, axis=2)
        v = jnp.repeat(v, g, axis=2)
        if sq > 1:
            k = constrain(k, "batch", "seq", "act_heads", None)
            v = constrain(v, "batch", "seq", "act_heads", None)
        else:
            # decode: keep the (possibly seq-sharded) cache layout; q is a
            # single token — forcing head sharding here would all-gather
            # the whole cache instead of the tiny q.
            k = constrain(k, "batch", "kv_seq", None, None)
            v = constrain(v, "batch", "kv_seq", None, None)
    kv_chunk = min(kv_chunk, skv)
    q_pos = q_offset + jnp.arange(sq)

    if kv_len is None:
        kv_len = jnp.asarray(skv, jnp.int32)

    nc = -(-skv // kv_chunk)
    pad = nc * kv_chunk - skv
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))

    def block(kc, vc, start, m, l, acc):
        # bf16 matmul + explicit upcast: preferred_element_type=f32 makes
        # the *backward* ds->dq/dk dots produce fp32 cotangents that flow
        # into the residual stream and double every model-axis collective
        # (EXPERIMENTS.md §Perf iteration 4b)
        s = jnp.einsum("bqhd,bkhd->bhqk", q, kc).astype(jnp.float32) * scale
        kv_pos = start + jnp.arange(kv_chunk)
        mask = kv_pos[None, :] < kv_len
        if causal:
            mask = mask & (q_pos[:, None] >= kv_pos[None, :])
        s = jnp.where(mask, s, -jnp.inf)                     # (q, k) bcast
        m_new = jnp.maximum(m, s.max(-1))
        # guard fully-masked rows (m_new == -inf)
        m_safe = jnp.where(jnp.isinf(m_new), 0.0, m_new)
        p = jnp.exp(s - m_safe[..., None])
        p = jnp.where(mask, p, 0.0)
        corr = jnp.where(jnp.isinf(m), 0.0, jnp.exp(m - m_safe))
        l_new = l * corr + p.sum(-1)
        pv = jnp.einsum("bhqk,bkhd->bhqd", p.astype(v.dtype), vc,
                        preferred_element_type=jnp.float32)
        acc_new = acc * corr[..., None] + pv
        return m_new, l_new, acc_new

    if nc == 1:
        m0 = jnp.full((b, hq, sq), -jnp.inf, jnp.float32)
        l0 = jnp.zeros((b, hq, sq), jnp.float32)
        a0 = jnp.zeros((b, hq, sq, d), jnp.float32)
        m, l, acc = block(k, v, 0, m0, l0, a0)
    else:
        ks = jnp.moveaxis(k.reshape(b, nc, kv_chunk, hq, d), 1, 0)
        vs = jnp.moveaxis(v.reshape(b, nc, kv_chunk, hq, d), 1, 0)

        def body(carry, xs):
            m, l, acc = carry
            kc, vc, idx = xs
            m, l, acc = block(kc, vc, idx * kv_chunk, m, l, acc)
            return (m, l, acc), None

        init = (jnp.full((b, hq, sq), -jnp.inf, jnp.float32),
                jnp.zeros((b, hq, sq), jnp.float32),
                jnp.zeros((b, hq, sq, d), jnp.float32))
        (m, l, acc), _ = jax.lax.scan(body, init,
                                      (ks, vs, jnp.arange(nc)))

    out = acc / jnp.maximum(l, 1e-37)[..., None]             # (b,hq,sq,d)
    out = jnp.moveaxis(out, 2, 1)
    if return_stats:
        return out.astype(q.dtype), m, l                      # (b,hq,sq)
    return out.astype(q.dtype)


def merge_attention(parts):
    """Combine flash partials [(out, m, l), ...] over disjoint kv sets.

    out: (b, s, h, d); m/l: (b, h, s). The softmax-stats merge — used to
    attend over [static seq-sharded context cache] + [small replicated
    tail of decoded tokens] without dynamic updates into the sharded
    cache (a dynamic-index update on a model-sharded seq dim makes GSPMD
    all-gather the cache every layer; see EXPERIMENTS.md §Perf decode)."""
    ms = jnp.stack([m for _, m, _ in parts])                  # (p,b,h,s)
    m_star = jnp.max(ms, axis=0)
    num = 0.0
    den = 0.0
    for out, m, l in parts:
        w = (l * jnp.exp(m - m_star))                         # (b,h,s)
        num = num + jnp.moveaxis(w, 1, 2)[..., None] \
            * out.astype(jnp.float32)
        den = den + jnp.moveaxis(w, 1, 2)
    out = num / jnp.maximum(den, 1e-37)[..., None]
    return out.astype(parts[0][0].dtype)


# ----------------------------------------------------------------------------
# Attention layer
# ----------------------------------------------------------------------------


def attention_defs(cfg, *, stacked: int = 0, cross: bool = False) -> dict:
    d, hq, hkv, hd = (cfg.d_model, cfg.num_heads, cfg.num_kv_heads,
                      cfg.resolved_head_dim)
    pre = (stacked,) if stacked else ()
    pax = ("layers",) if stacked else ()
    defs = {
        "wq": ParamDef(pre + (d, hq, hd), pax + ("embed_fsdp", "heads", "head_dim")),
        "wk": ParamDef(pre + (d, hkv, hd), pax + ("embed_fsdp", "kv_heads", "head_dim")),
        "wv": ParamDef(pre + (d, hkv, hd), pax + ("embed_fsdp", "kv_heads", "head_dim")),
        "wo": ParamDef(pre + (hq, hd, d), pax + ("heads", "head_dim", "embed_fsdp"),
                       scale=0.02 / np.sqrt(2 * max(cfg.num_layers, 1))),
    }
    if cfg.qkv_bias:
        defs["bq"] = ParamDef(pre + (hq, hd), pax + ("heads", "head_dim"), init="zeros")
        defs["bk"] = ParamDef(pre + (hkv, hd), pax + ("kv_heads", "head_dim"), init="zeros")
        defs["bv"] = ParamDef(pre + (hkv, hd), pax + ("kv_heads", "head_dim"), init="zeros")
    return defs


def attention_qkv(cfg, p, x, positions=None, *, use_rope: bool = True):
    """Project to q, k, v (+bias, +rope). x: (b, s, d)."""
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"])
    if cfg.qkv_bias:
        q = q + p["bq"]
        k = k + p["bk"]
        v = v + p["bv"]
    if use_rope and positions is not None:
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, positions, cfg.rope_theta)
    q = constrain(q, "batch", "seq", "act_heads", None)
    k = constrain(k, "batch", "seq", "act_kv", None)
    v = constrain(v, "batch", "seq", "act_kv", None)
    return q, k, v


def attention_out(p, o):
    """o: (b, s, hq, hd) -> (b, s, d)."""
    return jnp.einsum("bshk,hkd->bsd", o, p["wo"])


# ----------------------------------------------------------------------------
# Dense / gated MLP
# ----------------------------------------------------------------------------


def mlp_defs(cfg, *, stacked: int = 0, d_ff: Optional[int] = None) -> dict:
    d, f = cfg.d_model, d_ff or cfg.d_ff
    pre = (stacked,) if stacked else ()
    pax = ("layers",) if stacked else ()
    gated = cfg.activation in ("swiglu", "geglu")
    defs = {
        "w_up": ParamDef(pre + (d, f), pax + ("embed_fsdp", "mlp")),
        "w_down": ParamDef(pre + (f, d), pax + ("mlp", "embed_fsdp"),
                           scale=0.02 / np.sqrt(2 * max(cfg.num_layers, 1))),
    }
    if gated:
        defs["w_gate"] = ParamDef(pre + (d, f), pax + ("embed_fsdp", "mlp"))
    return defs


def mlp_block(cfg, p, x):
    act = act_fn(cfg.activation)
    up = jnp.einsum("bsd,df->bsf", x, p["w_up"])
    if "w_gate" in p:
        gate = jnp.einsum("bsd,df->bsf", x, p["w_gate"])
        h = act(gate) * up
    else:
        h = act(up)
    h = constrain(h, "batch", "seq", "act_mlp")
    return jnp.einsum("bsf,fd->bsd", h, p["w_down"])


# ----------------------------------------------------------------------------
# Mixture of Experts (sort-based, dropping, GShard-capacity)
# ----------------------------------------------------------------------------


def moe_defs(cfg, *, stacked: int = 0) -> dict:
    assert cfg.moe is not None
    d, f, e = cfg.d_model, cfg.d_ff, cfg.moe.num_experts
    pre = (stacked,) if stacked else ()
    pax = ("layers",) if stacked else ()
    down_scale = 0.02 / np.sqrt(2 * max(cfg.num_layers, 1))
    defs = {
        "router": ParamDef(pre + (d, e), pax + ("embed", None)),
        "w_up": ParamDef(pre + (e, d, f), pax + ("experts", "embed_fsdp", "mlp")),
        "w_gate": ParamDef(pre + (e, d, f), pax + ("experts", "embed_fsdp", "mlp")),
        "w_down": ParamDef(pre + (e, f, d), pax + ("experts", "mlp", "embed_fsdp"),
                           scale=down_scale),
    }
    return defs


def moe_block(cfg, p, x):
    """x: (b, s, d) -> (y, aux_loss).

    Dispatches to the shard_map two-stage implementation when a mesh-rules
    context is active (auto-GSPMD partitioning of a global sort/gather
    dispatch replicates the token gather — measured 924 GiB/device on the
    235B config; see EXPERIMENTS.md §Perf). Falls back to the single-device
    sort-based implementation otherwise (smoke tests, oracles).
    """
    from repro.distributed.sharding import current_rules
    rules = current_rules()
    if rules is not None and rules.mesh.devices.size > 1:
        return moe_block_sharded(cfg, p, x, rules)
    return moe_block_local(cfg, p, x)


def moe_block_local(cfg, p, x):
    """Single-device sort-based dispatch with capacity (oracle path)."""
    mo = cfg.moe
    b, s, d = x.shape
    t = b * s
    e, k = mo.num_experts, mo.experts_per_token
    xf = x.reshape(t, d)
    xf = constrain(xf, "batch", None)

    logits = jnp.einsum("td,de->te", xf, p["router"]).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, gate_idx = jax.lax.top_k(probs, k)             # (t, k)
    gate_vals = gate_vals / jnp.maximum(
        gate_vals.sum(-1, keepdims=True), 1e-9)               # renormalize

    # load-balancing aux loss (Switch-style)
    me = probs.mean(0)                                        # (e,)
    assign_frac = jnp.zeros(e).at[gate_idx.reshape(-1)].add(1.0) / (t * k)
    aux = e * jnp.sum(me * assign_frac)

    # flatten (t, k) assignments and sort by expert
    tk = t * k
    eids = gate_idx.reshape(tk)
    tok = jnp.repeat(jnp.arange(t, dtype=jnp.int32), k)
    gflat = gate_vals.reshape(tk)
    order = jnp.argsort(eids)
    se, st, sg = eids[order], tok[order], gflat[order]

    counts = jnp.zeros(e, jnp.int32).at[se].add(1)
    starts = jnp.concatenate([jnp.zeros(1, jnp.int32),
                              jnp.cumsum(counts)[:-1]])
    pos = jnp.arange(tk, dtype=jnp.int32) - starts[se]

    cap = int(np.ceil(mo.capacity_factor * tk / e))
    cap = max(4, -(-cap // 4) * 4)
    keep = pos < cap
    pos_c = jnp.where(keep, pos, cap)                         # drop OOB

    buf = jnp.zeros((e, cap + 1, d), x.dtype)
    buf = buf.at[se, pos_c].set(
        jnp.where(keep[:, None], xf[st], 0).astype(x.dtype), mode="drop")
    buf = buf[:, :cap]
    buf = constrain(buf, "experts", "expert_cap", None)

    act = act_fn(cfg.activation)
    up = jnp.einsum("ecd,edf->ecf", buf, p["w_up"])
    gate = jnp.einsum("ecd,edf->ecf", buf, p["w_gate"])
    h = act(gate) * up
    h = constrain(h, "experts", "expert_cap", "act_mlp")
    out = jnp.einsum("ecf,efd->ecd", h, p["w_down"])
    out = constrain(out, "experts", "expert_cap", None)

    vals = out[se, pos_c]                                     # (tk, d)
    vals = jnp.where(keep[:, None], vals, 0)
    y = jnp.zeros((t, d), jnp.float32).at[st].add(
        vals.astype(jnp.float32) * sg[:, None])
    y = constrain(y, "batch", None)
    return y.reshape(b, s, d).astype(x.dtype), aux


def moe_block_sharded(cfg, p, x, rules):
    """Two-stage MoE dispatch under shard_map (Megatron-EP style).

    Tokens are sharded over the data axes and replicated over the model
    axis; each device routes *locally*:
      EP mode (E % model == 0): device (i, j) keeps only assignments whose
        expert lives in model-column j, compacts them into an
        (E_loc, C_loc, d) buffer, runs its expert slices, scatters partial
        outputs back to local token order, and psums over "model".
      TP mode (E not divisible): every device processes all local
        assignments against d_ff-sharded expert weights; the down-proj
        contraction is partial over f and the same psum combines it.

    Two weight layouts (rules.table["embed_fsdp"] decides):
      FSDP (training): expert weights sharded on the embed dim over the
        data axes, all-gathered inside per use (weights travel).
      weight-stationary (decode): weights keep their f-dim shard; the
        *tokens* are all-gathered over the data axes instead (a few MB at
        decode vs ~27 GB/step of weight gathers on the 235B config) and
        the f-partial down-projection psums over data.
    """
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    mo = cfg.moe
    mesh = rules.mesh
    b, s, d = x.shape
    e, k = mo.num_experts, mo.experts_per_token
    data_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    n_data = 1
    for a in data_axes:
        n_data *= mesh.shape[a]
    msize = mesh.shape.get("model", 1)
    ep = e % msize == 0 and msize > 1
    e_loc = e // msize if ep else e
    ws = rules.weight_stationary and bool(data_axes)

    t_route = ((b // n_data) * s) if not ws else b * s
    cap = int(np.ceil(mo.capacity_factor * t_route * k / e))
    cap = max(8, -(-cap // 8) * 8)

    # weight specs mirror the rules resolution of moe_defs axes
    w_up_spec = rules.spec(("experts", "embed_fsdp", "mlp"),
                           p["w_up"].shape)
    w_dn_spec = rules.spec(("experts", "mlp", "embed_fsdp"),
                           p["w_down"].shape)
    act = act_fn(cfg.activation)

    def body(xl, router, w_up, w_gate, w_down):
        # xl: (b_loc, s, d); router: (d, e); w_*: local expert slices
        ax_model = "model" if msize > 1 else None
        j = jax.lax.axis_index(ax_model) if ep else 0
        bl = xl.shape[0]
        xf = xl.reshape(bl * s, d)

        if ws:
            # decode: gather the (tiny) token batch; weights stay put
            xf = jax.lax.all_gather(xf, data_axes, axis=0, tiled=True)
        elif data_axes:
            w_up = jax.lax.all_gather(w_up, data_axes, axis=1, tiled=True)
            w_gate = jax.lax.all_gather(w_gate, data_axes, axis=1,
                                        tiled=True)
            w_down = jax.lax.all_gather(w_down, data_axes, axis=2,
                                        tiled=True)
        tl = xf.shape[0]

        logits = jnp.einsum("td,de->te", xf, router).astype(jnp.float32)
        probs = jax.nn.softmax(logits, axis=-1)
        gate_vals, gate_idx = jax.lax.top_k(probs, k)
        gate_vals = gate_vals / jnp.maximum(
            gate_vals.sum(-1, keepdims=True), 1e-9)

        me = probs.mean(0)
        frac = jnp.zeros(e).at[gate_idx.reshape(-1)].add(1.0) / (tl * k)
        aux = e * jnp.sum(me * frac)

        tk = tl * k
        eids = gate_idx.reshape(tk)
        tok = jnp.repeat(jnp.arange(tl, dtype=jnp.int32), k)
        gflat = gate_vals.reshape(tk)

        if ep:
            mine = (eids // e_loc) == j
            local_eid = jnp.where(mine, eids - j * e_loc, e_loc)
        else:
            mine = jnp.ones(tk, bool)
            local_eid = eids
        order = jnp.argsort(jnp.where(mine, local_eid, e_loc + 1))
        se, st, sg = local_eid[order], tok[order], gflat[order]
        valid = se < e_loc if ep else jnp.ones(tk, bool)

        counts = jnp.zeros(e_loc + 2, jnp.int32).at[se].add(1)
        starts = jnp.concatenate([jnp.zeros(1, jnp.int32),
                                  jnp.cumsum(counts)[:-1]])
        pos = jnp.arange(tk, dtype=jnp.int32) - starts[se]
        keep = valid & (pos < cap)
        pos_c = jnp.where(keep, pos, cap)
        se_c = jnp.minimum(se, e_loc - 1)

        buf = jnp.zeros((e_loc, cap + 1, d), xl.dtype)
        buf = buf.at[se_c, pos_c].set(
            jnp.where(keep[:, None], xf[st], 0).astype(xl.dtype),
            mode="drop")
        buf = buf[:, :cap]

        up = jnp.einsum("ecd,edf->ecf", buf, w_up)
        gate = jnp.einsum("ecd,edf->ecf", buf, w_gate)
        if ws:
            # f-dim sharded over data: every data shard holds the same
            # (gathered) tokens, so the partial down-projection psums
            up = act(gate) * up
            out = jnp.einsum("ecf,efd->ecd", up, w_down)
            out = jax.lax.psum(out, data_axes)
        else:
            h = act(gate) * up
            out = jnp.einsum("ecf,efd->ecd", h, w_down)

        vals = out[se_c, pos_c]
        vals = jnp.where(keep[:, None], vals, 0)
        y = jnp.zeros((tl, d), jnp.float32).at[st].add(
            vals.astype(jnp.float32) * sg[:, None])
        if msize > 1:
            y = jax.lax.psum(y, "model")
        if data_axes:
            aux = jax.lax.pmean(aux, data_axes)
        if ws:
            # take back this shard's own token rows
            didx = 0
            for a in data_axes:
                didx = didx * mesh.shape[a] + jax.lax.axis_index(a)
            y = jax.lax.dynamic_slice_in_dim(y, didx * (bl * s), bl * s, 0)
        return y.reshape(bl, s, d).astype(xl.dtype), aux

    y, aux = shard_map(
        body, mesh=mesh,
        in_specs=(P(data_axes, None, None), P(None, None),
                  w_up_spec, w_up_spec, w_dn_spec),
        out_specs=(P(data_axes, None, None), P()),
        check_vma=False,
    )(x, p["router"], p["w_up"], p["w_gate"], p["w_down"])
    return y, aux

"""The model the cell serves: the program's model built at the sizes of
the configuration file, and random weights drawn from the seed.

The weights are the benchmark's own, drawn on the device in one jitted
call in the dtype they are served in, and laid out as the program's
parameter tree. The reference reads the same arrays; it takes nothing the
program made.
"""
from __future__ import annotations

import dataclasses

import numpy as np

VECTOR_STD = 0.05       # norm-weight offsets and biases


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


def _std(path: str, shape: tuple, conf: dict) -> float:
    if path in ("scale", "bq", "bk", "bv"):
        return VECTOR_STD                      # norms and biases
    if path in ("emb", "unemb"):
        fan_in = conf["hidden_size"]
    elif path == "wo":
        fan_in = shape[1] * shape[2]           # (layers, heads, hd, d)
    else:
        fan_in = shape[1]                      # (layers, in, ...)
    std = fan_in ** -0.5
    if path in ("wo", "w_down"):
        std /= np.sqrt(2 * conf["num_hidden_layers"])
    return std


def key_data(seed: int) -> np.ndarray:
    """Four 32-bit words for an ``rbg`` key, from a seed of any size."""
    return np.random.SeedSequence(seed).generate_state(4, np.uint32)


def draw(conf: dict, seed: int):
    """Random bf16 weights in the program's layout, on the default device,
    in one compiled call."""
    import jax
    import jax.numpy as jnp

    tree = shapes(conf)
    flat, treedef = jax.tree.flatten(tree, is_leaf=lambda x: isinstance(
        x, tuple))
    names = [str(p[-1].key) for p, _ in jax.tree_util.tree_flatten_with_path(
        tree, is_leaf=lambda x: isinstance(x, tuple))[0]]
    stds = [_std(nm, s, conf) for nm, s in zip(names, flat)]

    @jax.jit
    def make(kd):
        keys = jax.random.split(jax.random.wrap_key_data(kd, impl="rbg"),
                                len(flat))
        return [(jax.random.normal(k, s, jnp.float32) * sd).astype(
            jnp.bfloat16) for k, s, sd in zip(keys, flat, stds)]

    return jax.tree.unflatten(treedef, make(jnp.asarray(key_data(seed))))


def program_model(conf: dict):
    """The program's model at the configuration file's sizes. Raises
    where the program's parameter tree differs from ``shapes(conf)``."""
    import jax
    from repro.configs import get_config
    from repro.models import build_model

    cfg = dataclasses.replace(
        get_config(conf["program_arch"]), name=conf["name"],
        num_layers=conf["num_hidden_layers"], d_model=conf["hidden_size"],
        num_heads=conf["num_attention_heads"],
        num_kv_heads=conf["num_key_value_heads"], head_dim=conf["head_dim"],
        d_ff=conf["intermediate_size"], vocab_size=conf["vocab_size"],
        qkv_bias=conf["attention_bias"], rope_theta=float(conf["rope_theta"]),
        tie_embeddings=conf["tie_word_embeddings"], activation="swiglu",
        norm="rmsnorm", moe=None)
    model = build_model(cfg)
    want = jax.tree.map(lambda s: tuple(s.shape), model.abstract_params())
    have = jax.tree.map(lambda s: s, shapes(conf),
                        is_leaf=lambda x: isinstance(x, tuple))
    if want != have:
        raise ValueError(f"program parameter tree {want} differs from the "
                         f"configuration's {have}")
    return model

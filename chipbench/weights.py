"""The model the cell serves: the program's model built at the sizes of
the configuration file, and random weights drawn from the seed.

The weights are the benchmark's own, drawn on the device in one jitted
call in the dtype they are served in, and laid out as the program's
parameter tree, whose shapes and scales the configuration's model family
gives (``spec.family``). The reference reads the same arrays; it takes
nothing the program made.
"""
from __future__ import annotations

import numpy as np

from chipbench import spec


def shapes(conf: dict) -> dict:
    """Leaf shapes of the program's parameter tree, from the published
    sizes in a configuration file."""
    return spec.family(conf).shapes(conf)


def key_data(seed: int) -> np.ndarray:
    """Four 32-bit words for an ``rbg`` key, from a seed of any size."""
    return np.random.SeedSequence(seed).generate_state(4, np.uint32)


def draw(conf: dict, seed: int):
    """Random bf16 weights in the program's layout, on the default device,
    in one compiled call."""
    import jax
    import jax.numpy as jnp

    fam = spec.family(conf)
    tree = fam.shapes(conf)
    flat, treedef = jax.tree.flatten(tree, is_leaf=lambda x: isinstance(
        x, tuple))
    names = [str(p[-1].key) for p, _ in jax.tree_util.tree_flatten_with_path(
        tree, is_leaf=lambda x: isinstance(x, tuple))[0]]
    stds = [fam.init_std(nm, s, conf) for nm, s in zip(names, flat)]

    @jax.jit
    def make(kd):
        keys = jax.random.split(jax.random.wrap_key_data(kd, impl="rbg"),
                                len(flat))
        return [(jax.random.normal(k, s, jnp.float32) * sd).astype(
            jnp.bfloat16) for k, s, sd in zip(keys, flat, stds)]

    return jax.tree.unflatten(treedef, make(jnp.asarray(key_data(seed))))


def program_config(conf: dict):
    """The program's ``ModelConfig`` at the configuration file's sizes.
    Raises ``spec.NoFamily`` where the family has no module or the
    program has no model for it."""
    cfg = spec.family(conf).program_config(conf)
    if cfg is None:
        raise spec.NoFamily(f"the program has no model for model family "
                            f"{spec.family_name(conf)!r} (configuration "
                            f"{conf.get('name')!r})")
    return cfg


def program_model(conf: dict):
    """The program's model at the configuration file's sizes. Raises
    where the program's parameter tree differs from ``shapes(conf)``."""
    import jax
    from repro.models import build_model

    model = build_model(program_config(conf))
    want = jax.tree.map(lambda s: tuple(s.shape), model.abstract_params())
    have = jax.tree.map(lambda s: s, shapes(conf),
                        is_leaf=lambda x: isinstance(x, tuple))
    if want != have:
        raise ValueError(f"program parameter tree {want} differs from the "
                         f"configuration's {have}")
    return model

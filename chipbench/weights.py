"""Initial weights from the run's seed, made on the device in one jitted
call, in the layout and types the program trains.

The layout (which leaves, their shapes and types) is read from the
program's own parameter tree by ``jax.eval_shape``; the values are made
here, so the plain reference can rebuild them without the program. Leaf i
of the flattened tree draws from ``fold_in(key, i)``:

  * norm scales (float32 leaves named ``scale``): ones;
  * biases (``bias``): zeros;
  * the token embedding and the head: N(0, 0.02);
  * every other matrix: N(0, 1/fan_in), fan_in = its second-to-last size.

A model whose config ties the embedding and the head starts with
``lm_head = embed.T``, as the program's ``untie_params`` makes it.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def run_key(seed: int) -> jax.Array:
    """The run's raw threefry key: the seed's high and low 32-bit words,
    so that seeds past 2**32 stay distinct."""
    return jnp.asarray([(seed >> 32) & 0xFFFFFFFF, seed & 0xFFFFFFFF],
                       jnp.uint32)


def path_name(path) -> str:
    return "/".join(str(getattr(p, "key", p)) for p in path)


def make_fn(shapes, tied: bool):
    """A jitted ``key -> params`` for the layout ``shapes`` (a pytree of
    ShapeDtypeStructs with an ``embed`` and an ``lm_head`` leaf)."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)

    def build(key):
        out = []
        for i, (path, s) in enumerate(flat):
            name = path_name(path)
            leaf = name.rsplit("/", 1)[-1]
            k = jax.random.fold_in(key, i)
            if leaf == "scale":
                x = jnp.ones(s.shape, jnp.float32)
            elif leaf == "bias":
                x = jnp.zeros(s.shape, jnp.float32)
            else:
                std = (0.02 if name in ("embed", "lm_head")
                       else 1.0 / math.sqrt(s.shape[-2]))
                x = jax.random.normal(k, s.shape, jnp.float32) * std
            out.append(x.astype(s.dtype))
        params = jax.tree_util.tree_unflatten(treedef, out)
        if tied:
            params["lm_head"] = params["embed"].T
        return params
    return jax.jit(build)


def program_layout(cfg):
    """The program's untied parameter tree as shapes (nothing allocated)."""
    from repro.models import init_params, untie_params
    return jax.eval_shape(
        lambda: untie_params(cfg, init_params(cfg, jax.random.PRNGKey(0))))

"""Weights drawn by the benchmark from the seed, on the device, in one jitted
call, in the dtype the program serves them in.

The program only lends its parameter layout (leaf names, shapes, dtypes and
the sharding its rules give each leaf); the values come from here, so the
plain reference and the program read the same numbers and the reference
takes nothing the program made.  Each leaf is drawn by its name:

- matrices: truncated normal (two sigma) with std 1/sqrt(fan-in); the
  embedding with the configuration's ``embedding_std``.  The fan-in comes
  from the leaf's logical axes (``model_init``'s second tree), with the
  axes that only stack copies (``layer``, ``experts``) left out: a leaf
  whose last axis is ``embed`` writes back into the model width and reads
  every axis before it (attention's out-projection reads heads x head
  size); any other reads its first axis;
- norm scales: 1 + 0.1 N(0, 1); biases (one axis beside the stacking
  ones): ``bias_std`` N(0, 1), so a bias path is exercised (zero biases
  would hide it).  Attention's per-head biases (``bq``, ``bk``, ``bv``:
  heads x head size) have two axes and are drawn as matrices over their
  first, std 1/sqrt(heads), as the benchmark has drawn them since its
  limits were set;
- Mamba2's ``A_log``, ``dt_bias`` and ``D`` as the Mamba2 paper initialises
  them: A in [1, 16], dt in [1e-3, 1e-1] log-uniform, D in [0.5, 1.5].
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def _key(k) -> str:
    return str(getattr(k, "key", getattr(k, "idx", k)))


# axes that hold stacked copies of a matrix, not inputs or outputs of it
STACKING = ("layer", "experts")


def fan_in(axes, shape) -> int:
    """The inputs a matrix leaf's matmul sums over, from its logical
    ``axes``: every axis before a trailing ``embed``, else the first, the
    stacking axes left out."""
    sizes = [n for a, n in zip(axes, shape) if a not in STACKING]
    axes = [a for a in axes if a not in STACKING]
    reads = sizes[:-1] if axes[-1] == "embed" else sizes[:1]
    return math.prod(reads)


def _draw_leaf(name, axes, leaf, key, draw: dict):
    shape, dtype = leaf.shape, leaf.dtype
    inner = [a for a in axes if a not in STACKING]
    u = lambda lo, hi: jax.random.uniform(key, shape, jnp.float32, lo, hi)
    if name in ("scale", "norm"):
        v = 1.0 + 0.1 * jax.random.normal(key, shape, jnp.float32)
    elif name == "A_log":
        v = jnp.log(u(1.0, 16.0))
    elif name == "dt_bias":
        dt = jnp.exp(u(math.log(1e-3), math.log(1e-1)))
        v = dt + jnp.log(-jnp.expm1(-dt))            # softplus^-1(dt)
    elif name == "D":
        v = u(0.5, 1.5)
    elif len(inner) == 1:                            # biases
        v = float(draw["bias_std"]) * jax.random.normal(key, shape,
                                                         jnp.float32)
    else:
        if name == "embedding":
            std = float(draw["embedding_std"])
        elif name.startswith("conv"):
            std = 0.5                                 # depthwise, width 4
        else:
            std = 1.0 / math.sqrt(fan_in(axes, shape))
        v = std * jax.random.truncated_normal(key, -2.0, 2.0, shape,
                                              jnp.float32)
    return v.astype(dtype)


def leaf_axes(axes_tree, path) -> tuple:
    """The logical axes ``model_init`` gives the leaf at ``path``."""
    for k in path:
        axes_tree = axes_tree[getattr(k, "key", getattr(k, "idx", None))]
    return tuple(axes_tree)


def draw(model_init, cfg, seed: int, draw_spec: dict, rules=None):
    """Weights for ``cfg`` in the layout ``model_init(cfg, key)`` gives, placed
    as ``rules`` shards that layout (one device where ``rules`` is None)."""
    box = {}

    def layout(k):
        p, box["axes"] = model_init(cfg, k)
        return p

    abstract = jax.eval_shape(layout, jax.random.PRNGKey(0))
    flat, treedef = jax.tree_util.tree_flatten_with_path(abstract)
    shardings = (None if rules is None
                 else rules.tree_shardings(box["axes"], abstract))

    def make(key):
        leaves = [_draw_leaf(_key(path[-1]), leaf_axes(box["axes"], path),
                             leaf, jax.random.fold_in(key, i), draw_spec)
                  for i, (path, leaf) in enumerate(flat)]
        return jax.tree_util.tree_unflatten(treedef, leaves)

    return jax.jit(make, out_shardings=shardings)(seed_key(seed))


def seed_key(seed: int):
    """A PRNG key that keeps every bit of a seed wider than 32 bits."""
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              seed >> 32)

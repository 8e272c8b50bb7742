"""``shard_map`` facade.

Every region in the repo goes through here so that its body runs inside
``ctx.manual_axes``: ``shard_act`` then knows which mesh axes it must not
constrain over.
"""
from __future__ import annotations

import functools

import jax

from repro.dist import ctx


def shard_map(f=None, *, mesh, in_specs, out_specs, axis_names=None,
              check_vma: bool = True):
    """``jax.shard_map`` with the body run under ``ctx.manual_axes``.

    ``axis_names``: the *manual* mesh axes (default: all of them); the rest
    stay auto (GSPMD).
    Usable directly or via ``functools.partial(shard_map, mesh=..., ...)``.
    """
    if f is None:
        return functools.partial(shard_map, mesh=mesh, in_specs=in_specs,
                                 out_specs=out_specs, axis_names=axis_names,
                                 check_vma=check_vma)
    manual = (frozenset(axis_names) if axis_names is not None
              else frozenset(mesh.axis_names))

    @functools.wraps(f)
    def body(*args):
        with ctx.manual_axes(manual):
            return f(*args)

    return jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, axis_names=set(manual),
                         check_vma=check_vma)

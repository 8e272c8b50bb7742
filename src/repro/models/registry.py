"""Family -> model module dispatch."""
from __future__ import annotations

import jax

from repro.models import encdec, hybrid, lm, ssm_lm

_FAMILY_MODULES = {
    "dense": lm,
    "moe": lm,
    "vlm": lm,
    "ssm": ssm_lm,
    "hybrid": hybrid,
    "encdec": encdec,
}


def get_model(cfg):
    return _FAMILY_MODULES[cfg.family]


def init_params(cfg, key, rules=None):
    """``model.init`` as one jitted program: the parameters are drawn on
    the device, with no float32 draw or per-layer copy kept beside the
    stacked result.  With ``rules`` every leaf is created sharded as its
    logical axes say.  Returns (params, axes)."""
    model = get_model(cfg)
    axes = {}

    def draw(k):
        params, axes["tree"] = model.init(cfg, k)
        return params

    shapes = jax.eval_shape(draw, key)
    out = (None if rules is None
           else rules.tree_shardings(axes["tree"], shapes))
    return jax.jit(draw, out_shardings=out)(key), axes["tree"]

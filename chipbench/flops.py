"""Operations a decode token requires, counted from the model's shapes.

The arithmetic is that of ``repro.launch.flops_model`` (decode branch), with
two differences that make it the work a token needs rather than what the
program executes: heads are counted unpadded, and attention reads the
token's real context, not the page capacity the program gathers.

Nothing here dispatches on a family: each family's reference module,
``reference/<family>.py``, gives the count of its lanes as
``lane_flops(cfg, p0, p1)``, built from these formulas or from its own.
"""
from __future__ import annotations


def dense_token_flops(c, context: float) -> float:
    """One token of a dense decoder attending ``context`` positions."""
    d, hd, L = c.d_model, c.hd, c.num_layers
    nq, nkv = c.num_heads, c.num_kv_heads
    attn_proj = 2.0 * d * hd * (2 * nq + 2 * nkv)
    attn_score = 2.0 * 2.0 * nq * hd * context            # qk + pv
    mlp = 2.0 * 3.0 * d * c.d_ff
    return L * (attn_proj + attn_score + mlp) + 2.0 * d * c.vocab_size


def ssm_token_flops(c) -> float:
    """One token of a Mamba2 decoder: projections and the O(1) recurrence."""
    d, di = c.d_model, c.d_inner
    G, N, H, P = c.ssm_groups, c.ssm_state, c.ssm_heads, c.ssm_head_dim
    per = (2.0 * d * (2 * di + 2 * G * N + H) + 2.0 * di * d
           + 2.0 * 2.0 * H * P * N)
    return c.num_layers * per + 2.0 * d * c.vocab_size


def dense_lane_flops(c, p0: int, p1: int) -> float:
    """A lane of a dense decoder that moved from position ``p0`` to ``p1``
    in one megastep: the token at position p attends the p + 1 positions
    0..p."""
    n = int(p1) - int(p0)
    if n <= 0:
        return 0.0
    # sum over p in [p0, p1) of (p + 1)
    ctx = (p0 + 1 + p1) * n / 2.0
    return n * dense_token_flops(c, 0.0) + (
        c.num_layers * 2.0 * 2.0 * c.num_heads * c.hd * ctx)


def ssm_lane_flops(c, p0: int, p1: int) -> float:
    """A lane of a Mamba2 decoder that moved from ``p0`` to ``p1``: the
    same work at every position."""
    n = int(p1) - int(p0)
    return n * ssm_token_flops(c) if n > 0 else 0.0

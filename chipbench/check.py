"""The comparison that decides ``correct``.

Once the window has closed, a sample of the requests the run finished,
drawn from the seed and holding the one with the most served tokens, is run
through the plain float32 reference: each prompt followed by the tokens the
program served.  At the position before each served token the reference
gives its logits; the gap of that token is how far its logit lies below the
reference's largest.  Greedy serving that computes what the configuration
states picks a token at or next to the reference's best, so the widest gap
over the sample stays small; a wrong token, a wrong cache read or a lane
left unserved shows as a wide one.

The control (``control_gaps``) is the reference in float8, the step below
the bfloat16 the configurations serve in, put in the program's place: at
the same positions of the same sequences, the gap of the token that the
float8 model puts first.  Only the calibration runs it.
"""
from __future__ import annotations

import math
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

# the sample: the longest finished request and then others, drawn from the
# seed, until this many served tokens are in it (or MAX_REQUESTS are)
SAMPLE_TOKENS = 512
MAX_REQUESTS = 8
# served tokens at the start of each sampled request for ``early_mean_gap``
EARLY_TOKENS = 48


def sample(finished: Sequence, rng: np.random.Generator) -> List:
    """Requests to compare: the one with the most served tokens, then
    others in an order drawn from ``rng``."""
    done = [r for r in finished if len(r.sampled) > 0]
    if not done:
        return []
    done.sort(key=lambda r: r.req_id)
    first = max(done, key=lambda r: (len(r.sampled), -r.req_id))
    rest = [done[i] for i in rng.permutation(len(done)) if done[i] is not first]
    out, tokens = [first], len(first.sampled)
    for r in rest:
        if tokens >= SAMPLE_TOKENS or len(out) >= MAX_REQUESTS:
            break
        out.append(r)
        tokens += len(r.sampled)
    return out


def _sequence(req) -> Tuple[np.ndarray, np.ndarray]:
    """(tokens fed: the prompt and every served token but the last; the
    token each position should pick, -1 inside the prompt)."""
    prompt = np.asarray(req.prompt, np.int32)
    served = np.asarray(req.sampled, np.int32)
    fed = np.concatenate([prompt, served[:-1]])
    picks = np.full(fed.size, -1, np.int32)
    picks[prompt.size - 1:] = served
    return fed, picks


@jax.jit
def _gaps(logits, picks):
    """Per row, how far the picked token's logit lies below the row's
    largest (0 where nothing is picked)."""
    picked = jnp.take_along_axis(logits, jnp.maximum(picks, 0)[:, None],
                                 1)[:, 0]
    return jnp.where(picks >= 0, logits.max(axis=1) - picked, 0.0)


def _pad(picks: np.ndarray, rows: int) -> jnp.ndarray:
    out = np.full(rows, -1, np.int32)
    out[:picks.size] = picks
    return jnp.asarray(out)


def token_gaps(ref, params, model: dict, finished: Sequence,
               rng: np.random.Generator) -> List[np.ndarray]:
    """For each request of the sample, the gap of each served token below
    the reference's best, in the order served."""
    out = []
    for r in sample(finished, rng):
        fed, picks = _sequence(r)
        exact = ref.logits(params, model, fed)
        gap = np.asarray(_gaps(exact, _pad(picks, exact.shape[0])))
        out.append(gap[len(r.prompt) - 1:fed.size])
    return out


def gap_numbers(gaps: List[np.ndarray]) -> dict:
    """The numbers compared from the per-token gaps: ``logit_gap``, the
    widest over the sample, and ``early_mean_gap``, the mean over the first
    ``EARLY_TOKENS`` served tokens of each sampled request, where a lane
    state that its previous request left behind shows most; ``inf`` for
    both when the run finished no request."""
    if not gaps:
        return {"logit_gap": math.inf, "early_mean_gap": math.inf}
    return {"logit_gap": max(float(g.max()) for g in gaps),
            "early_mean_gap": float(np.concatenate(
                [g[:EARLY_TOKENS] for g in gaps]).mean())}


def control_gaps(ref, params, model: dict, finished: Sequence,
                 rng: np.random.Generator) -> Tuple[float, float, int]:
    """(program's widest gap, control's widest gap, tokens compared) over
    the same sample."""
    reqs = sample(finished, rng)
    if not reqs:
        return math.inf, math.inf, 0
    prog = ctrl = 0.0
    n = 0
    for r in reqs:
        fed, picks = _sequence(r)
        exact = ref.logits(params, model, fed)
        rows = exact.shape[0]
        prog = max(prog, float(_gaps(exact, _pad(picks, rows)).max()))
        low = np.asarray(ref.logits(params, model, fed, quant=True)
                         .argmax(axis=1)).astype(np.int32)
        low = np.where(_pad(picks, rows) >= 0, low, -1)
        ctrl = max(ctrl, float(_gaps(exact, jnp.asarray(low)).max()))
        n += len(r.sampled)
        del exact
    return prog, ctrl, n


def passes(checks: dict) -> bool:
    """A run is correct when no number compared is above its limit."""
    return all(v["value"] <= v["limit"] for v in checks.values())


def judge(limits: dict, numbers: dict, *, unserved: int,
          block_table_mismatches: Optional[int]) -> dict:
    """Each number compared, beside its limit: of ``numbers``, those the
    configuration gives a limit."""
    out = {name: {"value": v, "limit": float(limits[name])}
           for name, v in numbers.items() if name in limits}
    out["unserved"] = {"value": unserved, "limit": 0}
    if block_table_mismatches is not None:
        out["block_table_mismatches"] = {"value": block_table_mismatches,
                                         "limit": 0}
    return out

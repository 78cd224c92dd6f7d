"""Whether what the timed path served is what the model computes.

Once the window has closed, a sample of the finished requests, drawn from
the seed with the longest among them, is run through the plain
reference: one forward over each prompt followed by the tokens served
for it.  At every served token the reference's logit for that token is
compared with the reference's best logit at that position; the widest
such gap over the sample is the number compared.  Greedy serving that
computes the model right stays within rounding of 0; a token altered,
a cache page written or read wrong, or a tick that drops its state,
opens the gap by the size of the logits themselves.

The control puts the reference in the program's place at the precision
below the one the configuration states (float8 matrices for bfloat16):
at each position of the same prompts and tokens it takes the token the
float8 logits put first, and reads that token's gap.
"""
from __future__ import annotations

from typing import List, Sequence

import numpy as np

#: the sample grows until it holds this many served tokens and this many
#: requests, or reaches the cap
SAMPLE_TOKENS = 400
SAMPLE_MIN_REQUESTS = 4
SAMPLE_REQUESTS = 8


def sample(finished: Sequence, rng: np.random.Generator,
           min_tokens: int = SAMPLE_TOKENS,
           max_requests: int = SAMPLE_REQUESTS) -> List:
    """The longest finished request, one that found shared pages (where
    any did), then others in an order drawn from ``rng``."""
    if not finished:
        return []
    pool = list(finished)
    longest = max(pool, key=lambda r: (len(r.generated), -r.rid))
    picked = [longest]
    shared = [r for r in pool if r is not longest and r.shared_pages]
    if shared:
        picked.append(shared[int(rng.integers(len(shared)))])
    rest = [r for r in pool if all(r is not p for p in picked)]
    for i in rng.permutation(len(rest)):
        if len(picked) >= max_requests or (
                sum(len(r.generated) for r in picked) >= min_tokens
                and len(picked) >= SAMPLE_MIN_REQUESTS):
            break
        picked.append(rest[int(i)])
    return picked


def _positions(prompt_len: int, n_served: int) -> np.ndarray:
    return np.arange(prompt_len - 1, prompt_len - 1 + n_served)


def gaps(reference, params, cfg: dict, prompt: Sequence[int],
         served: Sequence[int], control: bool = False) -> np.ndarray:
    """Per served position, the reference's best logit less its logit for
    the served token (``control``: for the token float8 puts first)."""
    import jax.numpy as jnp
    tokens = list(prompt) + list(served[:-1])
    pos = _positions(len(prompt), len(served))
    ref = reference.logits(params, cfg, tokens, pos)
    if control:
        pick = jnp.argmax(reference.logits(params, cfg, tokens, pos,
                                           quant="fp8"), axis=-1)
    else:
        pick = jnp.asarray(np.asarray(served, np.int32))
    chosen = jnp.take_along_axis(ref, pick[:, None], axis=-1)[:, 0]
    return np.asarray(jnp.max(ref, axis=-1) - chosen)

"""How ``correct`` is decided: served tokens against the plain reference.

Once the window has closed, a sample drawn from the seed of the requests
the timed path finished (the longest among them always in it) is run
through the configuration's reference once, in float32, over each prompt
followed by its served tokens.  The number compared is the widest gap by
which a served token's reference logit lies below the reference's best
logit at that position.  Greedy decoding in bfloat16 picks a token within
rounding of the best, so sound runs read small gaps; a token altered where
it is produced reads a gap of the order of the logits' spread.

The control puts the reference at int8 in the program's place: at the same
served positions it picks the token the int8 reference ranks first, and
that pick is scored and judged exactly as a served token is (``verdict``).
"""

from __future__ import annotations

from typing import List, Tuple

import numpy as np

from .spec import load_module

SAMPLE_REQUESTS = 6     # requests compared per run, the longest among them
PAD_TO = 256            # sequence lengths round up to this (fewer shapes)


def draw_sample(done: List[Tuple[np.ndarray, list]], seed: int,
                min_tokens: int = 0, k: int = SAMPLE_REQUESTS
                ) -> List[Tuple[np.ndarray, list]]:
    """Finished (prompt, served tokens) pairs: the one with the most served
    tokens, then others in an order drawn from the seed, until the sample
    holds ``k`` requests and ``min_tokens`` served tokens (or all)."""
    if not done:
        return []
    longest = max(range(len(done)), key=lambda i: (len(done[i][1]), -i))
    rest = [i for i in range(len(done)) if i != longest]
    rng = np.random.default_rng(np.random.SeedSequence([int(seed), 3]))
    pick, tokens = [longest], len(done[longest][1])
    for i in rng.permutation(rest).tolist():
        if len(pick) >= k and tokens >= min_tokens:
            break
        pick.append(i)
        tokens += len(done[i][1])
    return [done[i] for i in [longest] + sorted(pick[1:])]


def pack(sample):
    """tokens (N, S) of prompt + served[:-1], and for each position the
    served token it should predict (-1 where none), right-padded."""
    s = max(len(p) + len(g) - 1 for p, g in sample)
    s = -(-s // PAD_TO) * PAD_TO
    tokens = np.zeros((len(sample), s), np.int32)
    want = np.full((len(sample), s), -1, np.int32)
    for i, (p, g) in enumerate(sample):
        seq = np.concatenate([np.asarray(p, np.int32),
                              np.asarray(g[:-1], np.int32)])
        tokens[i, : len(seq)] = seq
        want[i, len(p) - 1: len(p) - 1 + len(g)] = g
    return tokens, want


def served_gaps(params, config: dict, sample) -> np.ndarray:
    """Reference best logit minus the served token's, per served token."""
    ref = load_module("reference", config["reference"])
    tokens, want = pack(sample)
    best, picked, _ = ref.forward(params, config, tokens,
                                  np.maximum(want, 0)[..., None])
    mask = want >= 0
    return (best - picked[..., 0])[mask]


def control_gaps(params, config: dict, sample) -> np.ndarray:
    """The control in the program's place: at each served position of the
    same prompts and served tokens, the reference's best logit minus its
    logit of the token the int8 reference puts first."""
    ref = load_module("reference", config["reference"])
    tokens, want = pack(sample)
    _, _, arg = ref.forward(params, config, tokens,
                            np.zeros(tokens.shape + (1,), np.int32),
                            int8=True)
    best, picked, _ = ref.forward(params, config, tokens, arg[..., None])
    return (best - picked[..., 0])[want >= 0]


def verdict(gaps: np.ndarray, failed: int, attempted: int, limits: dict):
    """(correct, checks): each number compared beside its limit."""
    gap = float(gaps.max()) if gaps.size else None
    checks = {
        "served_gap_max": {"value": gap, "limit": limits["served_gap_max"]},
        "served_tokens": {"value": int(gaps.size),
                          "limit": limits["served_tokens_min"]},
        "failed": {"value": failed, "limit": 0},
    }
    correct = (gap is not None and gap <= limits["served_gap_max"]
               and gaps.size >= limits["served_tokens_min"]
               and failed == 0 and attempted > 0)
    return bool(correct), checks

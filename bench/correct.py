"""The comparison that decides ``correct``.

Once the window has closed, a sample of the requests the server served,
in the warm-up and in the window, is drawn from the seed, the longest
(prompt plus served tokens) always in it, until it holds ``check_tokens``
served tokens or every served request.  A served request is one that
finished, or one still in a slot when its stream stopped, with the tokens
it had been delivered: where a window finishes few requests (long prompts
at a token or two a second), the tokens delivered to the unfinished ones
are most of what the timed path produced.
The reference runs once over each prompt followed by its served tokens.
For each served token, its gap is the reference's best logit at that
position less the reference's logit of the served token (0 where the server
chose the reference's best).  The number compared is the widest gap; it must
stay at or under the cell's ``gap_limit`` (``checks``).

The control puts the reference, computed in float8, in the server's place:
at each served position the token the float8 forward ranks first has a gap
in the float32 reference, and those gaps go through the same ``checks``.
"""
from __future__ import annotations

from typing import Dict, Hashable, List, Sequence, Tuple

import numpy as np


def sample(finished: Dict[Hashable, Tuple[np.ndarray, List[int]]],
           seed: int, check_tokens: int) -> List[Hashable]:
    """Request keys to compare: the longest first, then others in an order
    drawn from the seed, until ``check_tokens`` served tokens are in."""
    if not finished:
        return []
    rids = sorted(finished)
    longest = rids[max(range(len(rids)),
                       key=lambda i: (len(finished[rids[i]][0])
                                      + len(finished[rids[i]][1]), -i))]
    rng = np.random.default_rng(np.random.SeedSequence([int(seed), 7]))
    rest = [r for r in rids if r != longest]
    order = [longest] + [rest[i] for i in rng.permutation(len(rest))]
    out, n = [], 0
    for r in order:
        if n >= check_tokens:
            break
        out.append(r)
        n += len(finished[r][1])
    return out


def sequences(finished, rids: Sequence[int]):
    """For each request: prompt + served tokens, and the positions whose
    logits predict the served tokens."""
    seqs, want = [], []
    for r in rids:
        prompt, served = finished[r]
        seqs.append(np.concatenate([np.asarray(prompt, np.int32),
                                    np.asarray(served, np.int32)]))
        want.append(np.arange(len(prompt) - 1, len(prompt) - 1 + len(served)))
    return seqs, want


def served_gaps(ref_logits: Sequence[np.ndarray], finished,
                rids: Sequence[int]) -> np.ndarray:
    """Gap of every served token in the reference's logits."""
    out = []
    for lg, r in zip(ref_logits, rids):
        served = np.asarray(finished[r][1])
        out.append(lg.max(-1) - lg[np.arange(len(served)), served])
    return np.concatenate(out) if out else np.zeros((0,))


def control_gaps(ref_logits, ctl_logits) -> np.ndarray:
    """Gap, in the reference, of the token the control ranks first."""
    out = []
    for lg, cl in zip(ref_logits, ctl_logits):
        top = cl.argmax(-1)
        out.append(lg.max(-1) - lg[np.arange(len(top)), top])
    return np.concatenate(out) if out else np.zeros((0,))


def checks(gaps: np.ndarray, limit: float, act_tokens: int,
           failed: int) -> Dict[str, Dict]:
    """The numbers compared, each with its limit and whether it holds.
    ``correct`` is true where every one holds."""
    widest = float(gaps.max()) if len(gaps) else float("inf")
    return {
        "widest_logit_gap": {"value": widest, "limit": limit,
                             "holds": widest <= limit},
        "served_tokens_compared": {"value": int(len(gaps)), "limit": 1,
                                   "holds": len(gaps) >= 1},
        "act_tokens_in_compared": {"value": int(act_tokens), "limit": 1,
                                   "holds": act_tokens >= 1},
        "failed_requests": {"value": int(failed), "limit": 0,
                            "holds": failed == 0},
    }

"""The comparison that decides ``correct``.

Once the window has closed, a sample of the requests the program
finished, drawn from the seed and always holding the one with the most
served tokens, goes through the plain reference with its prompt and the
tokens the program served.  The number compared is the widest gap by
which a served token's reference logit lies below the reference's best
at that position (``max_logit_gap``); the configuration file gives its
limit.  Sequences are padded to the cache's length and run in batches
of a fixed size, so the reference compiles once.
"""

from __future__ import annotations

import numpy as np

from chipbench import reference

BATCH = 4


def sample(finished: dict, seed: int, want_tokens: int) -> list:
    """Request ids to check: the longest, then others in an order drawn
    from ``seed``, until ``want_tokens`` served tokens are in."""
    if not finished:
        return []
    rids = sorted(finished)
    longest = max(rids, key=lambda r: (len(finished[r][1]), -r))
    rest = [r for r in rids if r != longest]
    rest = [rest[i] for i in np.random.default_rng(seed).permutation(
        len(rest))]
    out, n = [], 0
    for r in [longest] + rest:
        if n >= want_tokens:
            break
        out.append(r)
        n += len(finished[r][1])
    return out


def batches(finished: dict, rids: list, length: int):
    """(tokens, targets) [BATCH, length] int32 arrays: prompt + served
    tokens, and each served token at the position that predicts it."""
    for i in range(0, len(rids), BATCH):
        tokens = np.zeros((BATCH, length), np.int32)
        targets = np.full((BATCH, length), -1, np.int32)
        for b, r in enumerate(rids[i:i + BATCH]):
            prompt, served = finished[r]
            seq = np.concatenate([prompt, served])[:length]
            tokens[b, :len(seq)] = seq
            P = len(prompt)
            targets[b, P - 1:P - 1 + len(served)] = served
        yield tokens, targets


def widest_gaps(params, dm, finished: dict, rids: list, length: int,
                bits: int | None = None, logits=reference.logits
                ) -> tuple[float, float | None, int]:
    """(served gap, control gap or None, served tokens compared), by the
    architecture's reference ``logits`` at its sizes ``dm``."""
    served = ctrl = 0.0
    n = 0
    for tokens, targets in batches(finished, rids, length):
        g, c = reference.gaps(params, tokens, targets, dm=dm, bits=bits,
                              logits=logits)
        served = max(served, float(np.max(np.asarray(g))))
        if c is not None:
            ctrl = max(ctrl, float(np.max(np.asarray(c))))
        n += int(np.sum(targets >= 0))
    return served, (ctrl if bits is not None else None), n

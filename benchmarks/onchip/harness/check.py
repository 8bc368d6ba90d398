"""The comparison that decides ``correct``.

Two kinds of number are compared, each against a limit of its own:

* ``lp_gap``: over a sample of the rows the window finished (drawn from the
  seed, the longest row always in it), the widest gap between the
  behaviour log-probability the program returned for a served token and
  the plain float32 reference's log-probability of that token given the
  same prompt and preceding served tokens.  It covers the verify forward
  (reused prefix), the compacted cache and resumed decode (continuation)
  and the assembly that lines them up.
* exact counts, limit 0: ``rows_off``, rows whose shape breaks what the
  traffic guarantees (reused prefix equal to the draft up to the planned
  rejection, a fully reused row equal to its draft, a length that ends at
  EOS or at the budget, padding after it), and ``reused_off``, how far the
  program's own count of reused tokens lies from the planned one.
"""
from __future__ import annotations

from typing import Dict, List, Sequence

import numpy as np

from .traffic import EOS_ID, PAD_ID


def row_ok(resp: np.ndarray, length: int, N: int, vocab: int,
           draft: np.ndarray = None, planned_n: int = 0,
           full_reuse: bool = False, draft_len: int = 0) -> bool:
    L = int(length)
    if not 1 <= L <= N:
        return False
    body = resp[:L]
    if np.any(body < 0) or np.any(body >= vocab) or np.any(resp[L:] != PAD_ID):
        return False
    if np.any(body[:-1] == EOS_ID) or not (L == N or body[-1] == EOS_ID):
        return False
    if draft is not None:
        if full_reuse:
            return L == draft_len and np.array_equal(body, draft[:L])
        if planned_n >= L or not np.array_equal(body[:planned_n],
                                                draft[:planned_n]):
            return False
    return True


def sample_rows(lengths: Sequence[int], k: int, seed: int) -> List[int]:
    """k row indices drawn from the seed, the (first) longest always in."""
    n = len(lengths)
    longest = int(np.argmax(lengths))
    rest = [i for i in range(n) if i != longest]
    rng = np.random.default_rng([int(seed) % (1 << 63), 3])
    pick = rng.choice(len(rest), size=min(k - 1, len(rest)), replace=False)
    return [longest] + sorted(rest[i] for i in pick)


def lp_gap(prog_lp: Sequence[np.ndarray], ref_lp: Sequence[np.ndarray]) -> float:
    """Widest |program - reference| over the served tokens of each row."""
    gap = 0.0
    for p, r in zip(prog_lp, ref_lp):
        if len(p):
            gap = max(gap, float(np.max(np.abs(np.asarray(p, np.float64)
                                               - np.asarray(r, np.float64)))))
    return gap


def verdict(values: Dict[str, float], limits: Dict[str, float]) -> bool:
    """Correct iff every number is finite and within its limit."""
    return all(np.isfinite(values[k]) and values[k] <= limits[k]
               for k in limits)


def lines(values: Dict[str, float], limits: Dict[str, float]) -> List[str]:
    return [f"check {k}: {values[k]!r} (limit {limits[k]!r})" for k in limits]

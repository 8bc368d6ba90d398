"""The one traffic generator.  A traffic mix is a JSON file of parameters
under ``traffic/``; every batch is a function of (seed, batch index) alone.

A batch is one or more GRPO groups: ``group_size`` samples of one prompt.
Prompt lengths cycle through ``prompt_len.cycle`` evenly spaced values in
[min, max], in an order drawn from the seed, so every seed sends the same
set of lengths.  Prompt ids are drawn from [3, vocab), so no prompt token
is PAD (0), BOS (1) or EOS (2).

``previous_epoch`` (optional) makes the batch's prompts carry trajectories
from an earlier epoch, to be written into the rollout cache before the
batch is collected:

* ``full_reuse_lengths``: one row per entry; the draft has that length, ends
  in EOS, and its behaviour log-probs lie far below any the current policy
  can give, so every token is accepted and the row is reused whole;
* ``reject_positions``: one row per entry; a full-budget draft whose
  log-probs are far below the policy's before the position (accepted) and
  far above it from the position on (rejected), so verification rejects
  exactly there whatever the numerics.

Which row gets which role is drawn per batch from the seed.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import List, Optional

import numpy as np

PAD_ID, EOS_ID = 0, 2
FIRST_ID = 3          # ids below are PAD, BOS and EOS
ACCEPT_LP = -1.0e4     # behaviour log-prob far below the policy's: accepted
REJECT_LP = 1.0e4      # far above it: rejected (acceptance prob ~exp(-1e4))
KEY_STRIDE = 1 << 20   # cache keys of batch i: [i * KEY_STRIDE, ...)


@dataclass
class Batch:
    index: int
    tokens: np.ndarray               # (B, P) int32, left-padded
    mask: np.ndarray                 # (B, P) bool
    cache_keys: List[int]
    prompt_len: int
    # previous-epoch trajectories (None on a first-epoch mix)
    draft_tokens: Optional[np.ndarray] = None    # (B, N) int32
    draft_logprobs: Optional[np.ndarray] = None  # (B, N) float32
    draft_len: Optional[np.ndarray] = None       # (B,) int32
    # what verification must give per row: the accepted prefix length
    # (== draft_len for a full-reuse row), and whether the row is reused whole
    planned_n: Optional[np.ndarray] = None       # (B,) int32
    full_reuse: Optional[np.ndarray] = None      # (B,) bool


class Traffic:
    def __init__(self, spec: dict, seed: int, vocab_size: int):
        self.seed = int(seed) % (1 << 63)
        self.vocab = int(vocab_size)
        self.G = int(spec["group_size"])
        self.groups = int(spec.get("prompts_per_batch", 1))
        self.B = self.G * self.groups
        self.N = int(spec["max_new_tokens"])
        pl = spec["prompt_len"]
        self.P = int(pl["pad_to"])
        cycle = int(pl["cycle"])
        self.lengths = np.round(np.linspace(pl["min"], pl["max"], cycle)
                                ).astype(np.int64)
        if self.lengths.max() > self.P or self.lengths.min() < 1:
            raise ValueError("prompt lengths must lie in [1, pad_to]")
        self.order = np.random.default_rng([self.seed, 1]).permutation(cycle)
        prev = spec.get("previous_epoch")
        self.full_lens = [] if not prev else list(prev["full_reuse_lengths"])
        self.rejects = [] if not prev else list(prev["reject_positions"])
        self.has_previous = bool(prev)
        if prev:
            if len(self.full_lens) + len(self.rejects) != self.G:
                raise ValueError("previous_epoch must give every row of a "
                                 "group one role")
            if not all(1 <= n <= self.N for n in self.full_lens) or \
                    not all(0 <= r < self.N for r in self.rejects):
                raise ValueError("draft lengths / reject positions outside "
                                 "the token budget")

    @property
    def cycle(self) -> int:
        """Batches in which every prompt length comes once."""
        return len(self.order)

    def batch(self, i: int) -> Batch:
        rng = np.random.default_rng([self.seed, 2, int(i)])
        B, G, P, N = self.B, self.G, self.P, self.N
        p_len = int(self.lengths[self.order[i % len(self.order)]])
        tokens = np.zeros((B, P), np.int32)
        mask = np.zeros((B, P), bool)
        for g in range(self.groups):
            prompt = rng.integers(FIRST_ID, self.vocab, size=p_len,
                                  dtype=np.int32)
            tokens[g * G:(g + 1) * G, P - p_len:] = prompt
            mask[g * G:(g + 1) * G, P - p_len:] = True
        keys = [int(i) * KEY_STRIDE + r for r in range(B)]
        out = Batch(index=int(i), tokens=tokens, mask=mask, cache_keys=keys,
                    prompt_len=p_len)
        if not self.has_previous:
            return out
        d_tok = rng.integers(FIRST_ID, self.vocab, size=(B, N),
                             dtype=np.int32)
        d_lp = np.full((B, N), ACCEPT_LP, np.float32)
        d_len = np.full((B,), N, np.int32)
        planned = np.zeros((B,), np.int32)
        full = np.zeros((B,), bool)
        roles = [("full", n) for n in self.full_lens] + \
                [("reject", r) for r in self.rejects]
        for g in range(self.groups):
            for j, r in enumerate(rng.permutation(G)):
                kind, v = roles[r]
                row = g * G + j
                if kind == "full":
                    d_len[row] = v
                    d_tok[row, v - 1] = EOS_ID
                    d_tok[row, v:] = PAD_ID
                    d_lp[row, v:] = 0.0
                    planned[row] = v
                    full[row] = True
                else:
                    d_lp[row, v:] = REJECT_LP
                    planned[row] = v
        out.draft_tokens, out.draft_logprobs, out.draft_len = d_tok, d_lp, d_len
        out.planned_n, out.full_reuse = planned, full
        return out


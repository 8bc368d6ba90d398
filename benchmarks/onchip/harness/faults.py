"""Faults planted in the program underneath a run, to show that the
comparison deciding ``correct`` catches them.  Each is a context manager
that patches the program while it is open and drops every compiled
program on entry and exit, since the patched functions are traced into
jitted ones.

* ``altered_token``: each sampled token is moved by 7 ids where the decode
  loop samples it, its log-prob left as sampled;
* ``half_batch``: the collection step returns the second half of its rows
  empty (no tokens, length 0), as a step that left half of the batch out.

A state left unchanged and a missing exchange between chips cannot occur
in a one-chip collection step, which has no optimizer state and no
collective.
"""
from __future__ import annotations

import contextlib

import numpy as np


def _clear():
    import jax
    jax.clear_caches()


@contextlib.contextmanager
def altered_token():
    import repro.engine.generate as G
    sample = G.sample

    def altered(key, logits, temperature=1.0, top_p=1.0):
        tok, lp = sample(key, logits, temperature, top_p)
        return (tok + 7) % logits.shape[-1], lp

    G.sample = altered
    _clear()
    try:
        yield
    finally:
        G.sample = sample
        _clear()


@contextlib.contextmanager
def half_batch():
    from repro.rl.trainer import Collector
    rollout_once = Collector.rollout_once

    def half(self, params, batch, epoch):
        rb = rollout_once(self, params, batch, epoch)
        h = len(rb.length) // 2
        for f in ("response", "response_mask", "behaviour_logprobs",
                  "length"):
            a = np.array(getattr(rb, f))
            a[h:] = 0
            setattr(rb, f, a)
        return rb

    Collector.rollout_once = half
    try:
        yield
    finally:
        Collector.rollout_once = rollout_once


FAULTS = {"altered_token": altered_token, "half_batch": half_batch}

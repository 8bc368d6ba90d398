"""The comparison that decides ``correct``, on a tiny model on the CPU:
a sound run is correct; the float8 control fails the ``lp_gap`` limit; and
a whole run of the harness (everything but the look for a chip) reads
``correct`` false when the timed path is broken underneath: a token
altered where it is sampled, or half of the batch's rows left out."""
import time

import jax
import pytest

import onchip_tiny as T
from harness import cell as C
from harness import check, faults

SEEDS = (11, 2 ** 31 + 11)


@pytest.fixture(autouse=True)
def _fresh_compiles():
    # faults are patched into functions that jit traces: drop every
    # compiled program before and after each test
    jax.clear_caches()
    yield
    jax.clear_caches()


def run(spec, seed):
    return C.run(T.CELL, T.ENTRY, spec, [], seed=seed, seconds=0.3,
                 trace_on=False, t_start=time.perf_counter(),
                 log=lambda s: None)


@pytest.mark.parametrize("mix", ["reuse", "fresh"])
def test_sound_run_is_correct(mix):
    spec = T.REUSE if mix == "reuse" else T.FRESH
    res = run(spec, SEEDS[0])
    assert res["correct"], res["check"]
    assert list(res)[-1] == "check"
    assert res["failed"] == 0 and res["attempted"] >= 8
    # the window holds whole cycles of the prompt lengths
    assert res["attempted"] % (spec["prompt_len"]["cycle"]
                               * spec["group_size"]) == 0
    assert set(res["metrics"]) == {"rollout_tokens_per_s", "setup_s"}


@pytest.mark.parametrize("seed", SEEDS)
def test_control_fails_the_limit(seed):
    s = C.Setup(T.ENTRY, T.REUSE, seed)
    recs = [C.collect(s.collector, s.params, s.traffic.batch(i),
                      time.perf_counter) for i in range(2)]
    rows = [(r, b) for r in recs for b in range(len(r.length))]
    prog, ref = C.reference_logprobs(s.ref, s.sizes, s.wkey, rows, s.traffic,
                                     "reference")
    _, ctl = C.reference_logprobs(s.ref, s.sizes, s.wkey, rows, s.traffic,
                                  "control")
    limits = C.limits_for(T.ENTRY)
    values = {"lp_gap": check.lp_gap(prog, ref), "rows_off": 0.0,
              "reused_off": 0.0}
    assert check.verdict(values, limits)
    assert not check.verdict(dict(values, lp_gap=check.lp_gap(ctl, ref)),
                             limits)


@pytest.mark.parametrize("mix", ["reuse", "fresh"])
def test_altered_token_is_not_correct(mix):
    with faults.altered_token():
        res = run(T.REUSE if mix == "reuse" else T.FRESH, SEEDS[1])
    assert not res["correct"]
    assert res["check"]["lp_gap"]["value"] > res["check"]["lp_gap"]["limit"]


@pytest.mark.parametrize("mix", ["reuse", "fresh"])
def test_half_batch_left_out_is_not_correct(mix):
    with faults.half_batch():
        res = run(T.REUSE if mix == "reuse" else T.FRESH, SEEDS[1])
    assert not res["correct"]
    assert res["failed"] > 0

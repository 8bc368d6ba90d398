"""The traffic generator: deterministic from the seed, the same sizes for
every seed, and (through the program's own rollout, on a tiny model on the
CPU) rejection exactly at the planned positions on the reuse mix and the
``generate`` path on the first-epoch mix."""
import time

import jax
import numpy as np
import pytest

import onchip_tiny as T
from harness import cell as C
from harness.traffic import EOS_ID, Traffic

SEED = 2 ** 31 + 977


@pytest.fixture(scope="module", autouse=True)
def _release_compiled():
    yield
    jax.clear_caches()


def test_same_seed_same_batches_and_every_seed_same_sizes():
    a, b = Traffic(T.REUSE, SEED, 512), Traffic(T.REUSE, SEED, 512)
    for i in (0, 1, 7, 1 << 30):
        x, y = a.batch(i), b.batch(i)
        for f in ("tokens", "mask", "draft_tokens", "draft_logprobs",
                  "draft_len", "planned_n", "full_reuse"):
            np.testing.assert_array_equal(getattr(x, f), getattr(y, f))
        assert x.cache_keys == y.cache_keys
    c = Traffic(T.REUSE, SEED + 1, 512)
    assert not np.array_equal(a.batch(0).tokens, c.batch(0).tokens)
    cycle = T.REUSE["prompt_len"]["cycle"]
    for t in (a, c):
        lens = sorted(t.batch(i).prompt_len for i in range(cycle))
        assert lens == [6, 9, 13, 16]
        roles = sorted(t.batch(0).planned_n.tolist())
        assert roles == [5, 10, 20, 30]


def test_batches_have_distinct_cache_keys_and_clean_prompts():
    t = Traffic(T.REUSE, SEED, 512)
    keys = set()
    for i in range(5):
        b = t.batch(i)
        assert not keys & set(b.cache_keys)
        keys |= set(b.cache_keys)
        assert b.tokens[b.mask].min() >= 3
        full = b.full_reuse
        assert np.all(b.draft_tokens[full, b.draft_len[full] - 1] == EOS_ID)
        assert not np.any(b.draft_tokens[~full] == EOS_ID)


@pytest.fixture(scope="module")
def reuse_setup():
    return C.Setup(T.ENTRY, T.REUSE, SEED)


def test_reuse_mix_rejects_exactly_at_planned_positions(reuse_setup):
    s = reuse_setup
    N = s.traffic.N
    for i in range(3):
        b = s.traffic.batch(i)
        r = C.collect(s.collector, s.params, b, time.perf_counter)
        assert r.times["one_pass"] == 1.0
        assert r.n_reused == int(b.planned_n.sum())
        for row in range(len(r.length)):
            n, resp = int(b.planned_n[row]), r.response[row]
            if b.full_reuse[row]:
                assert r.length[row] == b.draft_len[row]
                np.testing.assert_array_equal(resp[:n], b.draft_tokens[row, :n])
            else:
                mismatch = np.nonzero(resp[:N] != b.draft_tokens[row])[0]
                assert mismatch[0] == n
                assert r.length[row] == N or resp[r.length[row] - 1] == EOS_ID


def test_first_epoch_mix_takes_generate_path():
    s = C.Setup(T.ENTRY, T.FRESH, SEED)
    r = C.collect(s.collector, s.params, s.traffic.batch(0), time.perf_counter)
    assert r.times["one_pass"] == 0.0 and r.times["verify_time"] == 0.0
    assert r.n_reused == 0 and r.n_generated == int(r.length.sum())
    assert s.collector.cache.hits == 0


def _eos_logprobs(margin, seed=3):
    """The reference's log-prob of EOS after random prefixes of every
    length, under the tiny weights drawn with ``margin``."""
    import jax.numpy as jnp
    s = C.Setup(dict(T.ENTRY, eos_logit_margin=margin), T.FRESH, seed)
    V, R, Tn = T.ENTRY["config"]["vocab_size"], 8, 24
    rng = np.random.default_rng(seed)
    toks = rng.integers(3, V, size=(R, Tn)).astype(np.int32)
    lens = np.arange(Tn - R + 1, Tn + 1, dtype=np.int32)
    for r, n in enumerate(lens):
        toks[r, n - 1] = EOS_ID
    w = s.ref.init_weights(s.wkey, s.sizes)
    lp = np.asarray(s.ref.token_logprobs(w, s.sizes, jnp.asarray(toks),
                                         jnp.asarray(lens), "reference"))
    return lp[np.arange(R), lens - 1], np.log(V)


def test_eos_margin_holds_eos_below_every_token():
    plain, log_v = _eos_logprobs(0.0)
    held, _ = _eos_logprobs(6.0)
    # random weights give EOS about a uniform token's chance; the margin
    # takes some 6 nats (a lane's rms is estimated, not measured, per size)
    assert np.all(np.abs(plain + log_v) < 2.0)
    assert np.all(held < -log_v - 4.0) and np.all(held > -log_v - 9.0)


def test_with_the_eos_margin_rows_end_only_where_the_traffic_says():
    entry = dict(T.ENTRY, eos_logit_margin=6.0)
    s = C.Setup(entry, T.FRESH, SEED)
    N = s.traffic.N
    for i in range(3):
        r = C.collect(s.collector, s.params, s.traffic.batch(i),
                      time.perf_counter)
        assert np.all(r.length == N)
        assert not np.any(r.response[:, :N] == EOS_ID)
    s = C.Setup(entry, T.REUSE, SEED)
    b = s.traffic.batch(0)
    r = C.collect(s.collector, s.params, b, time.perf_counter)
    assert r.n_reused == int(b.planned_n.sum())
    full = b.full_reuse
    np.testing.assert_array_equal(r.length[full], b.draft_len[full])
    assert np.all(r.response[full, b.draft_len[full] - 1] == EOS_ID)
    assert np.all(r.length[~full] == N)

"""Serving engines: batched waves == per-sequence incremental reference,
and continuous batching == waves (same greedy tokens, fewer decode steps)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import reduced_config
from repro.models.transformer import (apply_model, init_cache,
                                      init_paged_cache, init_params)
from repro.serve.engine import (ContinuousServeEngine,
                                PagedContinuousServeEngine, Request,
                                ServeEngine, kv_block_bytes, poisson_arrivals)

KEY = jax.random.PRNGKey(0)


def greedy_reference(params, cfg, prompt, n_new, acfg=None):
    toks = jnp.asarray(prompt)[None, :]
    cache = init_cache(cfg, 1, len(prompt) + n_new + 2)
    logits, cache = apply_model(params, toks, cfg, acfg=acfg, cache=cache,
                                cache_pos=0)
    out = []
    cur = int(jnp.argmax(logits[0, -1]))
    pos = len(prompt)
    for _ in range(n_new):
        out.append(cur)
        logits, cache = apply_model(params, jnp.asarray([[cur]]), cfg,
                                    acfg=acfg, cache=cache, cache_pos=pos,
                                    decode=True)
        cur = int(jnp.argmax(logits[0, -1]))
        pos += 1
    return out


def test_engine_matches_reference():
    cfg = reduced_config("smollm-135m")
    params = init_params(KEY, cfg)
    prompt = np.asarray([5, 17, 3, 99], np.int32)
    ref = greedy_reference(params, cfg, prompt, 6)

    eng = ServeEngine(params, cfg, slots=2, max_seq=64)
    reqs = [Request(prompt=prompt, max_new_tokens=6),
            Request(prompt=prompt, max_new_tokens=6)]
    done = eng.run(reqs)
    for r in done:
        assert list(r.out) == ref


def test_mixed_length_wave_matches_solo():
    """Regression: left-pad slots must not leak into attention or shift RoPE
    positions — a short prompt decodes the same tokens whether it shares a
    wave with a much longer prompt or runs incrementally unpadded."""
    cfg = reduced_config("smollm-135m")
    params = init_params(KEY, cfg)
    short = np.asarray([7, 11, 2], np.int32)
    long = np.asarray([5, 17, 3, 99, 23, 41, 8, 1, 64, 12], np.int32)
    ref_short = greedy_reference(params, cfg, short, 6)
    ref_long = greedy_reference(params, cfg, long, 6)
    eng = ServeEngine(params, cfg, slots=2, max_seq=64)
    done = eng.run([Request(prompt=short, max_new_tokens=6),
                    Request(prompt=long, max_new_tokens=6)])
    assert list(done[0].out) == ref_short
    assert list(done[1].out) == ref_long


def test_no_trailing_decode_and_counts_unchanged():
    """Regression: the wave loop must not issue a decode step whose logits
    nothing consumes (N tokens need exactly N-1 decode calls after prefill),
    and the preallocated output buffer yields the same token counts."""
    cfg = reduced_config("smollm-135m")
    params = init_params(KEY, cfg)
    eng = ServeEngine(params, cfg, slots=2, max_seq=8)
    calls = [0]
    inner = eng._decode

    def counting(*a, **k):
        calls[0] += 1
        return inner(*a, **k)

    eng._decode = counting
    # budget = max_seq - plen = 6 caps max_new_tokens=10: the old loop ran a
    # 7th decode after collecting the 6th token because the slot never died
    done = eng.run([Request(prompt=np.asarray([3, 1], np.int32),
                            max_new_tokens=10)])
    assert len(done[0].out) == 6
    assert calls[0] == 5


def test_engine_multiple_waves_and_lengths():
    cfg = reduced_config("smollm-135m")
    params = init_params(KEY, cfg)
    reqs = [Request(prompt=np.asarray([i + 1, i + 2], np.int32),
                    max_new_tokens=3 + i) for i in range(5)]
    done = ServeEngine(params, cfg, slots=2, max_seq=32).run(reqs)
    for i, r in enumerate(done):
        assert len(r.out) == 3 + i
        assert all(0 <= t < cfg.vocab_padded for t in r.out)


# ---------------------------------------------------------------------------
# continuous batching
# ---------------------------------------------------------------------------

def _reqs(specs):
    return [Request(prompt=np.asarray(p, np.int32), max_new_tokens=n)
            for p, n in specs]


def test_continuous_matches_wave():
    """Continuous batching is a scheduling change, not a math change: the
    exact-path greedy tokens equal the wave engine's, mixed prompt lengths
    included."""
    cfg = reduced_config("smollm-135m")
    params = init_params(KEY, cfg)
    specs = [([5, 17, 3, 99], 6), ([7, 11, 2], 4),
             ([5, 17, 3, 99, 23, 41, 8, 1, 64, 12], 5), ([9, 9], 7)]
    wave = ServeEngine(params, cfg, slots=2, max_seq=64).run(_reqs(specs))
    cont = ContinuousServeEngine(params, cfg, slots=2,
                                 max_seq=64).run(_reqs(specs))
    for w, c in zip(wave, cont):
        assert list(w.out) == list(c.out)


def test_continuous_fewer_decode_steps():
    """The point of continuous batching: a freed slot admits the next queued
    request instead of idling behind the longest row of its wave, so mixed
    short/long budgets take strictly fewer decode steps."""
    cfg = reduced_config("smollm-135m")
    params = init_params(KEY, cfg)
    specs = [([3, 1], 3), ([4, 2], 12), ([5, 3], 3), ([6, 4], 12)]

    wave_eng = ServeEngine(params, cfg, slots=2, max_seq=32)
    calls = [0]
    inner = wave_eng._decode

    def counting(*a, **k):
        calls[0] += 1
        return inner(*a, **k)

    wave_eng._decode = counting
    wave = wave_eng.run(_reqs(specs))

    cont_eng = ContinuousServeEngine(params, cfg, slots=2, max_seq=32)
    cont = cont_eng.run(_reqs(specs))
    for w, c in zip(wave, cont):
        assert list(w.out) == list(c.out)
    assert cont_eng.stats["decode_steps"] < calls[0], \
        (cont_eng.stats["decode_steps"], calls[0])
    assert cont_eng.stats["tokens"] == sum(n for _, n in specs)


def test_continuous_per_request_budget_exact():
    """Regression (per-slot max_new_tokens): every request gets exactly its
    own budget even when short and long requests share the batch — no row
    over-generates to the batch max or under-generates to the batch min."""
    cfg = reduced_config("smollm-135m")
    params = init_params(KEY, cfg)
    budgets = [1, 9, 2, 7, 3]
    reqs = _reqs([([i + 1, i + 2], b) for i, b in enumerate(budgets)])
    eng = ContinuousServeEngine(params, cfg, slots=3, max_seq=32)
    done = eng.run(reqs)
    assert [len(r.out) for r in done] == budgets
    assert eng.stats["tokens"] == sum(budgets)


def test_continuous_approx_matches_straightline_decode():
    """ACU route end to end: a slots=1 continuous engine with a LUT-Pallas
    acfg emits exactly the tokens of straight-line apply_model calls using
    the same bucketed-prefill semantics (per-tensor activation scales depend
    on padding, so the reference pads identically)."""
    from repro.core.acu import make_acu
    from repro.core.approx_ops import ApproxConfig
    from repro.serve.engine import _bucket
    cfg = reduced_config("smollm-135m")
    params = init_params(KEY, cfg)
    acfg = ApproxConfig(acu=make_acu("mul8s_1L2H", use_pallas=True,
                                     fused=True))
    prompt, n_new, max_seq = [5, 17, 3, 99, 23], 5, 32

    bucket = _bucket(len(prompt))
    off = bucket - len(prompt)
    toks = np.zeros((1, bucket), np.int32)
    toks[0, off:] = prompt
    valid = np.zeros((1, max_seq), bool)
    valid[0, off:] = True
    cache = init_cache(cfg, 1, max_seq)
    logits, cache = apply_model(params, jnp.asarray(toks), cfg, acfg=acfg,
                                cache=cache, cache_pos=0,
                                pos_offset=jnp.asarray([off], jnp.int32),
                                pad_mask=jnp.asarray(valid), last_only=True)
    ref, cur, pos = [], int(jnp.argmax(logits[0, -1])), bucket
    for _ in range(n_new - 1):
        ref.append(cur)
        logits, cache = apply_model(
            params, jnp.asarray([[cur]]), cfg, acfg=acfg, cache=cache,
            cache_pos=jnp.asarray([pos], jnp.int32), decode=True,
            pos_offset=jnp.asarray([off], jnp.int32),
            pad_mask=jnp.asarray(valid))
        cur = int(jnp.argmax(logits[0, -1]))
        pos += 1
    ref.append(cur)

    eng = ContinuousServeEngine(params, cfg, slots=1, max_seq=max_seq,
                                acfg=acfg)
    done = eng.run(_reqs([(prompt, n_new)]))
    assert list(done[0].out) == ref


# ---------------------------------------------------------------------------
# paged KV + prefix reuse
# ---------------------------------------------------------------------------

def _fused_acfg():
    from repro.core.acu import make_acu
    from repro.core.approx_ops import ApproxConfig
    return ApproxConfig(acu=make_acu("mul8s_1L2H", use_pallas=True,
                                     fused=True))


def test_paged_matches_reference_exact():
    """Paged scheduling (block pool, chunked prefill, per-slot page tables)
    is invisible on the exact path: greedy tokens equal the incremental
    per-sequence reference, mixed prompt lengths included."""
    cfg = reduced_config("smollm-135m")
    params = init_params(KEY, cfg)
    specs = [([5, 17, 3, 99], 6), ([7, 11, 2], 4),
             ([5, 17, 3, 99, 23, 41, 8, 1, 64, 12], 5), ([9, 9], 7)]
    eng = PagedContinuousServeEngine(params, cfg, slots=2, max_seq=32,
                                     block_size=8)
    done = eng.run(_reqs(specs))
    for (p, n), r in zip(specs, done):
        assert list(r.out) == greedy_reference(
            params, cfg, np.asarray(p, np.int32), n)
    assert eng.stats["tokens"] == sum(n for _, n in specs)


def test_paged_prefix_reuse_bitwise():
    """The prefix-cache contract on the ACU route: a warm admission (full or
    partial prefix hit) emits tokens bit-identical to a cold run in a fresh
    engine — shared blocks hold exactly the KV a cold prefill would write,
    and the CoW'd full-prompt tail snapshot replays the cached first token."""
    _check_prefix_reuse(_fused_acfg())


def _leaky_acfg():
    """A fused ACU on a synthetic multiplier whose zero row depends on the
    other operand, ``M[0, w] = 7 + w // 16``: a masked key or an empty
    cache position contributes to every PV sum according to its V code."""
    import dataclasses

    from repro.core.acu import Acu, AcuMode
    from repro.core.approx_ops import ApproxConfig
    from repro.core.lut import build_lut
    from repro.core.multipliers import make_exact

    def leaky(a, w):
        a, w = a.astype(jnp.int32), w.astype(jnp.int32)
        return a * w + 7 + w // 16

    mult = dataclasses.replace(make_exact(8), name="mul8s_leaky", fn=leaky)
    acu = Acu(multiplier=mult, mode=AcuMode.LUT, lut=build_lut(mult),
              use_pallas=True, fused=True)
    assert acu.m00() == 7
    return ApproxConfig(acu=acu)


def test_paged_prefix_reuse_bitwise_biased():
    """The prefix-cache contract under the biased multiplier."""
    _check_prefix_reuse(_leaky_acfg())


def test_paged_decode_ignores_stale_block_tail():
    """A reused block's stale contents past the row's ``kv_len`` never
    reach the logits, even where masked positions contribute ``M[0, v]``:
    prefill writes positions 0-3 of physical block 2, decode writes 4, and
    5-7 keep whatever a previous owner left there."""
    cfg = reduced_config("smollm-135m")
    params = init_params(KEY, cfg)
    acfg = _leaky_acfg()
    pt = jnp.asarray([[2, 0, 0, 0]], jnp.int32)

    def prefill_decode(cache):
        _, cache = apply_model(params, jnp.asarray([[9, 2, 6, 5]], jnp.int32),
                               cfg, acfg=acfg, cache=cache,
                               cache_pos=jnp.asarray(0, jnp.int32),
                               page_table=pt)
        logits, _ = apply_model(params, jnp.asarray([[7]], jnp.int32), cfg,
                                acfg=acfg, cache=cache,
                                cache_pos=jnp.asarray([4], jnp.int32),
                                decode=True, page_table=pt)
        return logits

    clean = init_paged_cache(cfg, 4, 8)
    rng = np.random.default_rng(0)
    stale = jax.tree.map(lambda pool: pool.at[:, :, 2].set(jnp.asarray(
        rng.normal(size=pool[:, :, 2].shape) * 3, pool.dtype)), clean)
    assert jnp.array_equal(prefill_decode(clean), prefill_decode(stale))


def _check_prefix_reuse(acfg):
    cfg = reduced_config("smollm-135m")
    params = init_params(KEY, cfg)
    rng = np.random.default_rng(2)
    base = rng.integers(1, cfg.vocab_size, 20).astype(np.int32).tolist()
    ext = base + rng.integers(1, cfg.vocab_size, 5).astype(np.int32).tolist()

    def mk():
        return PagedContinuousServeEngine(params, cfg, slots=2, max_seq=64,
                                          block_size=8, acfg=acfg)

    cold_a = list(mk().run(_reqs([(base, 6)]))[0].out)
    cold_b = list(mk().run(_reqs([(ext, 6)]))[0].out)
    eng = mk()
    done = eng.run(_reqs([(base, 6), (base, 6), (ext, 6)]))
    assert list(done[0].out) == cold_a
    assert list(done[1].out) == cold_a          # full-prompt hit: zero prefill
    assert list(done[2].out) == cold_b          # partial hit: replayed tail
    assert eng.stats["full_prompt_hits"] == 1
    assert eng.stats["prefix_hit_blocks"] > 0


def test_over_length_rejected_both_engines():
    """Regression: a prompt longer than max_seq must be rejected at
    admission with an empty output (not crash an assert mid-run), and must
    not disturb the requests sharing its batch."""
    cfg = reduced_config("smollm-135m")
    params = init_params(KEY, cfg)
    ok = [5, 17, 3]
    ref = greedy_reference(params, cfg, np.asarray(ok, np.int32), 4)
    too_long = np.arange(1, 20, dtype=np.int32)     # 19 > max_seq = 16
    for mk in (lambda: ContinuousServeEngine(params, cfg, slots=2,
                                             max_seq=16),
               lambda: PagedContinuousServeEngine(params, cfg, slots=2,
                                                  max_seq=16, block_size=8)):
        eng = mk()
        done = eng.run([Request(prompt=too_long, max_new_tokens=4),
                        Request(prompt=np.asarray(ok, np.int32),
                                max_new_tokens=4)])
        assert len(done[0].out) == 0
        assert eng.stats["rejected"] == 1
        assert list(done[1].out) == ref


def test_paged_preemption_resumes_exactly():
    """Memory pressure: when the pool cannot grow a decoding row, the
    youngest request is preempted keeping its emitted tokens and re-queued
    with prompt+emitted — greedy decode is deterministic, so every output
    still equals the never-preempted reference."""
    cfg = reduced_config("smollm-135m")
    params = init_params(KEY, cfg)
    rng = np.random.default_rng(4)
    prompts = [rng.integers(1, cfg.vocab_size, 15).astype(np.int32)
               for _ in range(4)]
    refs = [greedy_reference(params, cfg, p, 20) for p in prompts]
    # 7 blocks total = null + scratch + 5 usable; each finished request
    # spans 5 blocks (35 tokens / 8), so two slots cannot both finish
    # without preempting
    eng = PagedContinuousServeEngine(
        params, cfg, slots=2, max_seq=40, block_size=8, prefix_cache=False,
        hbm_budget=7 * kv_block_bytes(cfg, 8))
    done = eng.run([Request(prompt=p, max_new_tokens=20) for p in prompts])
    for r, ref in zip(done, refs):
        assert list(r.out) == ref
    assert eng.stats["preemptions"] > 0


def test_paged_packs_more_rows_than_contiguous():
    """The point of paging: under the HBM budget of two contiguous rows, the
    paged engine still serves four short requests concurrently (occupancy
    above two slots) because rows only pin the blocks they actually use."""
    cfg = reduced_config("smollm-135m")
    params = init_params(KEY, cfg)
    budget = 2 * (64 // 8) * kv_block_bytes(cfg, 8)
    eng = PagedContinuousServeEngine(params, cfg, slots=4, max_seq=64,
                                     block_size=8, hbm_budget=budget)
    specs = [([i + 1, i + 2, i + 3], 6) for i in range(4)]
    done = eng.run(_reqs(specs))
    for (p, n), r in zip(specs, done):
        assert list(r.out) == greedy_reference(
            params, cfg, np.asarray(p, np.int32), n)
    assert eng.stats["occupancy"] > 2.0
    assert eng.stats["peak_blocks"] <= 2 * (64 // 8)


@pytest.mark.tier2
def test_paged_memory_pressure_trace():
    """Long staggered trace under real pressure on the ACU route: 10
    requests sharing a 32-token prefix against a budget of two contiguous
    rows for four slots — evictions and preemptions fire, yet every request
    gets its exact budget and the shared prefix keeps hitting."""
    cfg = reduced_config("smollm-135m")
    params = init_params(KEY, cfg)
    rng = np.random.default_rng(6)
    shared = rng.integers(1, cfg.vocab_size, 32).astype(np.int32)
    reqs = [Request(prompt=np.concatenate(
                [shared, rng.integers(1, cfg.vocab_size, 4).astype(np.int32)]),
                    max_new_tokens=8) for _ in range(10)]
    budget = 2 * (64 // 8) * kv_block_bytes(cfg, 8)
    eng = PagedContinuousServeEngine(params, cfg, slots=4, max_seq=64,
                                     block_size=8, acfg=_fused_acfg(),
                                     hbm_budget=budget)
    done = eng.run(reqs, arrivals=poisson_arrivals(len(reqs), rate=0.5,
                                                   seed=7))
    assert all(len(r.out) == 8 for r in done)
    assert eng.stats["prefix_hit_rate"] > 0.3
    assert eng.stats["peak_blocks"] <= 2 * (64 // 8)
    # determinism under pressure: same trace, fresh engine, same tokens
    reqs2 = [Request(prompt=r.prompt, max_new_tokens=8) for r in reqs]
    eng2 = PagedContinuousServeEngine(params, cfg, slots=4, max_seq=64,
                                      block_size=8, acfg=_fused_acfg(),
                                      hbm_budget=budget)
    done2 = eng2.run(reqs2, arrivals=poisson_arrivals(len(reqs), rate=0.5,
                                                      seed=7))
    for a, b in zip(done, done2):
        assert list(a.out) == list(b.out)


@pytest.mark.tier2
def test_continuous_poisson_trace():
    """Long staggered trace: every request served with its exact budget,
    arrivals respected (a request never produces tokens before it arrives),
    and the batch refills — occupancy above one slot on average."""
    cfg = reduced_config("smollm-135m")
    params = init_params(KEY, cfg)
    rng = np.random.default_rng(0)
    n = 16
    reqs = [Request(prompt=rng.integers(1, cfg.vocab_size,
                                        rng.integers(2, 9)).astype(np.int32),
                    max_new_tokens=int(rng.integers(2, 10)))
            for _ in range(n)]
    budgets = [r.max_new_tokens for r in reqs]
    arrivals = poisson_arrivals(n, rate=0.6, seed=3)
    eng = ContinuousServeEngine(params, cfg, slots=4, max_seq=32)
    done = eng.run(reqs, arrivals=arrivals)
    assert [len(r.out) for r in done] == budgets
    assert eng.stats["prefills"] == n
    assert eng.stats["occupancy"] > 1.0
    # same requests, all-at-once: tokens identical (arrival times only
    # reorder work, they cannot change any request's greedy decode)
    reqs2 = [Request(prompt=r.prompt, max_new_tokens=r.max_new_tokens)
             for r in reqs]
    done2 = ContinuousServeEngine(params, cfg, slots=4, max_seq=32).run(reqs2)
    for a, b in zip(done, done2):
        assert list(a.out) == list(b.out)

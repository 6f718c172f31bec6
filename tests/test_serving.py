"""Continuous-batching serving: slot-based decode with in-flight admission.

The scheduler must be a pure throughput optimization — every request's
tokens equal what the plain ``generate`` path produces for that prompt
alone, no matter when the request arrived, which slot served it, or what
else was in flight (the correctness bar vLLM-style batching has to clear).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dsml_tpu.models.gpt2 import GPT2, GPT2Config
from dsml_tpu.serving import ContinuousBatcher


def _prompts(cfg, lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, cfg.vocab_size, (l,)).astype(np.int32) for l in lengths]


def _reference(model, params, prompt, n):
    return [int(t) for t in np.asarray(model.generate(params, prompt[None, :], n))[0]]


def test_continuous_batching_matches_generate_gpt2():
    """Varied prompt lengths and token budgets, more requests than slots,
    staggered arrival: every request's greedy tokens equal the standalone
    generate output."""
    cfg = GPT2Config.tiny()
    model = GPT2(cfg)
    params = model.init(0)
    prompts = _prompts(cfg, [5, 17, 32, 9, 26])
    # budgets repeat values on purpose: each DISTINCT budget costs one
    # standalone-generate compile in the reference loop below; three
    # distinct lengths exercise the same retire/admit heterogeneity as five
    budgets = [5, 3, 6, 5, 3]

    srv = ContinuousBatcher(model, params, n_slots=2, prompt_buckets=(8, 16, 32))
    rids = [srv.submit(p, n) for p, n in zip(prompts[:3], budgets[:3])]
    srv.step()  # some work happens before the late arrivals
    rids += [srv.submit(p, n) for p, n in zip(prompts[3:], budgets[3:])]
    out = srv.run()

    for rid, prompt, n in zip(rids, prompts, budgets):
        assert out[rid] == _reference(model, params, prompt, n), rid


def test_slots_are_reused_as_requests_finish():
    """2 slots serve 4 requests to completion — retirement frees slots for
    the queue (the point of continuous batching)."""
    cfg = GPT2Config.tiny()
    model = GPT2(cfg)
    params = model.init(1)
    srv = ContinuousBatcher(model, params, n_slots=2, prompt_buckets=(8,))
    for p in _prompts(cfg, [4, 6, 5, 7], seed=1):
        srv.submit(p, 4)
    assert srv.n_queued == 4
    srv.step()
    assert srv.n_active <= 2  # never more than the slot count in flight
    out = srv.run()
    assert len(out) == 4 and all(len(t) == 4 for t in out.values())


def test_eos_retires_early():
    """A request stops at eos_id even with budget left; its slot frees."""
    cfg = GPT2Config.tiny()
    model = GPT2(cfg)
    params = model.init(2)
    prompt = _prompts(cfg, [6], seed=2)[0]
    # find what greedy emits, then declare its 2nd token the EOS
    ref = _reference(model, params, prompt, 5)
    eos = ref[1]
    srv = ContinuousBatcher(model, params, n_slots=1, eos_id=eos,
                            prompt_buckets=(8,))
    rid = srv.submit(prompt, 5)
    out = srv.run()
    expected = ref[: ref.index(eos) + 1]  # truncated at the FIRST eos
    assert out[rid] == expected and len(expected) < len(ref)


def test_continuous_batching_matches_generate_llama():
    """The per-slot path is model-generic: Llama's RoPE positions and GQA
    cache follow each slot's own depth."""
    from dsml_tpu.models.llama import Llama, LlamaConfig

    model = Llama(LlamaConfig.tiny())
    cfg = model.config
    params = model.init(3)
    prompts = _prompts(cfg, [7, 21, 12], seed=3)
    srv = ContinuousBatcher(model, params, n_slots=2, prompt_buckets=(8, 16, 32))
    rids = [srv.submit(p, 5) for p in prompts]
    out = srv.run()
    for rid, prompt in zip(rids, prompts):
        assert out[rid] == _reference(model, params, prompt, 5), rid


def test_temperature_sampling_is_slot_independent():
    """Sampled requests fold (rid, step) into the key, so tokens don't
    depend on scheduling: one-at-a-time equals all-at-once."""
    cfg = GPT2Config.tiny()
    model = GPT2(cfg)
    params = model.init(4)
    prompts = _prompts(cfg, [6, 11], seed=4)

    def serve(n_slots):
        srv = ContinuousBatcher(model, params, n_slots=n_slots, temperature=0.8,
                                seed=7, prompt_buckets=(16,))
        rids = [srv.submit(p, 4) for p in prompts]
        out = srv.run()
        return [out[r] for r in rids]

    assert serve(1) == serve(2)


@pytest.mark.slow
def test_top_k_top_p_sampling_is_schedule_independent():
    """top_k/top_p truncation rides the shared sample_token_logits (the
    same function generate uses), and stays slot/quantum-independent:
    tokens depend only on (seed, rid, step)."""
    cfg = GPT2Config.tiny()
    model = GPT2(cfg)
    params = model.init(21)
    prompts = _prompts(cfg, [6, 11, 8], seed=21)

    def serve(n_slots, quantum):
        srv = ContinuousBatcher(model, params, n_slots=n_slots, temperature=0.9,
                                top_k=12, top_p=0.8, seed=5,
                                prompt_buckets=(16,), decode_quantum=quantum)
        rids = [srv.submit(p, 5) for p in prompts]
        out = srv.run()
        return [out[r] for r in rids]

    assert serve(1, 1) == serve(2, 1) == serve(2, 4)


def test_step_streams_every_token_including_prefill_first():
    """A consumer accumulating step() returns sees EVERY token of every
    request — including each admission's prefill-sampled first token and
    requests that retire at prefill."""
    cfg = GPT2Config.tiny()
    model = GPT2(cfg)
    params = model.init(8)
    prompts = _prompts(cfg, [5, 9, 7], seed=8)
    budgets = [4, 1, 3]
    srv = ContinuousBatcher(model, params, n_slots=2, prompt_buckets=(16,))
    rids = [srv.submit(p, n) for p, n in zip(prompts, budgets)]
    streamed: dict = {}
    for _ in range(50):
        if not srv.n_queued and srv.n_active == 0:
            break
        for rid, toks in srv.step().items():
            streamed.setdefault(rid, []).extend(toks)
    assert streamed == srv.collect()
    for rid, p, n in zip(rids, prompts, budgets):
        assert streamed[rid] == _reference(model, params, p, n)


def test_decode_quantum_does_not_change_tokens():
    """decode_quantum is pure throughput tuning: greedy AND sampled tokens
    are identical for any quantum (the in-scan sampler folds the same
    (rid, step) keys the token-level path uses)."""
    cfg = GPT2Config.tiny()
    model = GPT2(cfg)
    params = model.init(6)
    prompts = _prompts(cfg, [5, 12, 8], seed=6)

    def serve(quantum, temperature):
        srv = ContinuousBatcher(model, params, n_slots=2, temperature=temperature,
                                seed=9, prompt_buckets=(8, 16),
                                decode_quantum=quantum)
        rids = [srv.submit(p, 7) for p in prompts]
        out = srv.run()
        return [out[r] for r in rids]

    a, b = serve(1, 0.0), serve(4, 0.0)
    assert a == b
    # greedy quantum path still equals standalone generate
    for tokens, p in zip(b, prompts):
        assert tokens == _reference(model, params, p, 7)


@pytest.mark.slow
def test_decode_quantum_full_matrix():
    """The full quantum × temperature matrix (the default run keeps the
    greedy 1-vs-4 representative): sampled tokens are also quantum-
    independent, including quantum 8 > every request's budget."""
    cfg = GPT2Config.tiny()
    model = GPT2(cfg)
    params = model.init(6)
    prompts = _prompts(cfg, [5, 12, 8], seed=6)

    def serve(quantum, temperature):
        srv = ContinuousBatcher(model, params, n_slots=2, temperature=temperature,
                                seed=9, prompt_buckets=(8, 16),
                                decode_quantum=quantum)
        rids = [srv.submit(p, 7) for p in prompts]
        out = srv.run()
        return [out[r] for r in rids]

    for temp in (0.0, 0.9):
        a, b, c = serve(1, temp), serve(4, temp), serve(8, temp)
        assert a == b == c, temp


def test_tp_sharded_batcher_matches_single_device(devices8):
    """mesh= makes the batcher tensor-parallel (Megatron params, head-
    sharded slot cache, shard_map prefill/decode) with IDENTICAL tokens."""
    from dsml_tpu.parallel.mesh import MeshSpec, build_mesh

    cfg = GPT2Config.tiny()
    model = GPT2(cfg)
    params = model.init(10)
    prompts = _prompts(cfg, [5, 17, 9, 26], seed=10)

    ref_srv = ContinuousBatcher(model, params, n_slots=2, prompt_buckets=(8, 32))
    ref_rids = [ref_srv.submit(p, 6) for p in prompts]
    ref = ref_srv.run()

    mesh = build_mesh(MeshSpec(tp=2), devices8[:2])
    srv = ContinuousBatcher(model, params, n_slots=2, prompt_buckets=(8, 32),
                            mesh=mesh, decode_quantum=3)
    rids = [srv.submit(p, 6) for p in prompts]
    out = srv.run()
    for r_ref, r_tp in zip(ref_rids, rids):
        assert ref[r_ref] == out[r_tp]
    # the slot cache is genuinely head-sharded over tp
    shard = srv._cache[0]["k"].addressable_shards[0]
    assert shard.data.shape[1] == cfg.n_head // 2


@pytest.mark.slow
def test_tp_sharded_batcher_llama_kv_quant(devices8):
    """The full serving composition: Llama GQA + int8 KV cache + TP sharding
    + continuous batching, tokens equal the single-device quantized batcher."""
    import dataclasses

    from dsml_tpu.models.llama import Llama, LlamaConfig
    from dsml_tpu.parallel.mesh import MeshSpec, build_mesh

    model = Llama(dataclasses.replace(LlamaConfig.tiny(), kv_quant=True))
    cfg = model.config
    params = model.init(11)
    prompts = _prompts(cfg, [7, 13], seed=11)

    ref_srv = ContinuousBatcher(model, params, n_slots=2, prompt_buckets=(16,))
    ref_rids = [ref_srv.submit(p, 5) for p in prompts]
    ref = ref_srv.run()

    mesh = build_mesh(MeshSpec(tp=2), devices8[:2])
    srv = ContinuousBatcher(model, params, n_slots=2, prompt_buckets=(16,), mesh=mesh)
    rids = [srv.submit(p, 5) for p in prompts]
    out = srv.run()
    for r_ref, r_tp in zip(ref_rids, rids):
        assert ref[r_ref] == out[r_tp]
    assert srv._cache[0]["k"].dtype == jnp.int8


def test_prefill_chunk_chain_matches_whole_prompt_prefill():
    """Model-level pin: chaining ceil(L/C) prefill_chunk calls reproduces
    prefill — logits at the true last position AND every cache row in
    [0, L) — for GPT-2, Llama (GQA+RoPE), and the int8 KV cache."""
    import dataclasses

    from dsml_tpu.models.llama import Llama, LlamaConfig

    cases = [
        (GPT2(GPT2Config.tiny()), 1e-4),
        (Llama(LlamaConfig.tiny()), 1e-4),
        # kv_quant: within-prompt attention reads int8 rows (whole-prompt
        # prefill attends exactly) — the documented chunked-prefill
        # approximation, so a looser but still tight bound
        (GPT2(dataclasses.replace(GPT2Config.tiny(), kv_quant=True)), 5e-2),
    ]
    for model, tol in cases:
        params = model.init(12)
        cfg = model.config
        rng = np.random.default_rng(12)
        L, C = 37, 16
        prompt = jnp.asarray(rng.integers(0, cfg.vocab_size, (1, L)), jnp.int32)
        ref_logits, ref_cache = model.prefill(params, prompt, last_index=L - 1)
        cache = model.init_cache(1)
        for i in range(-(-L // C)):
            s, e = i * C, min((i + 1) * C, L)
            padded = np.zeros((1, C), np.int32)
            padded[0, : e - s] = np.asarray(prompt[0, s:e])
            last = (L - 1) - s if e >= L else C - 1
            logits, cache = model.prefill_chunk(
                params, cache, jnp.asarray(padded), s, last_index=last
            )
        np.testing.assert_allclose(
            np.asarray(logits), np.asarray(ref_logits), atol=tol, rtol=0,
            err_msg=type(model).__name__,
        )

        def effective(entry):
            """Dequantized K/V rows [0, L) — the values attention consumes
            (raw int8 codes can differ by one step when the underlying
            float differs by rounding)."""
            if "k_s" in entry:
                return (
                    np.asarray(entry["k"][:, :, :L], np.float32)
                    * np.asarray(entry["k_s"][:, :, :L], np.float32),
                    np.asarray(entry["v"][:, :, :L], np.float32)
                    * np.asarray(entry["v_s"][:, :, :L], np.float32),
                )
            return (
                np.asarray(entry["k"][:, :, :L], np.float32),
                np.asarray(entry["v"][:, :, :L], np.float32),
            )

        for ref_c, c in zip(ref_cache, cache):
            for ref_arr, arr in zip(effective(ref_c), effective(c)):
                # layer 0 K/V is attention-free (exact); deeper rows pick up
                # accumulation-order rounding between the [L, L] whole-prompt
                # attention and the [C, S] chunk attention
                np.testing.assert_allclose(
                    ref_arr, arr, atol=tol, rtol=0, err_msg=type(model).__name__
                )


@pytest.mark.slow
def test_chunked_prefill_admission_matches_generate():
    """prefill_chunk is pure scheduling: greedy AND sampled tokens equal
    the whole-prompt batcher and the standalone generate path, across
    staggered arrivals and prompts spanning 1..4 chunks."""
    cfg = GPT2Config.tiny()
    model = GPT2(cfg)
    params = model.init(13)
    prompts = _prompts(cfg, [5, 30, 17, 58, 9], seed=13)
    budgets = [6, 4, 8, 3, 5]

    def serve(chunk, temperature):
        srv = ContinuousBatcher(model, params, n_slots=2, temperature=temperature,
                                seed=13, prompt_buckets=(8, 16, 32, 64),
                                prefill_chunk=chunk)
        rids = [srv.submit(p, n) for p, n in zip(prompts[:3], budgets[:3])]
        srv.step()
        rids += [srv.submit(p, n) for p, n in zip(prompts[3:], budgets[3:])]
        out = srv.run()
        return [out[r] for r in rids]

    chunked = serve(16, 0.0)
    assert chunked == serve(0, 0.0)
    for tokens, p, n in zip(chunked, prompts, budgets):
        assert tokens == _reference(model, params, p, n)


@pytest.mark.slow
def test_chunked_prefill_admission_matches_sampled():
    """Sampled (temperature) tokens are also chunk-independent — the
    rid-derived keys don't see the admission schedule. (Default run keeps
    the greedy representative above.)"""
    cfg = GPT2Config.tiny()
    model = GPT2(cfg)
    params = model.init(13)
    prompts = _prompts(cfg, [5, 30, 17, 58, 9], seed=13)
    budgets = [6, 4, 8, 3, 5]

    def serve(chunk):
        srv = ContinuousBatcher(model, params, n_slots=2, temperature=0.8,
                                seed=13, prompt_buckets=(8, 16, 32, 64),
                                prefill_chunk=chunk)
        rids = [srv.submit(p, n) for p, n in zip(prompts, budgets)]
        out = srv.run()
        return [out[r] for r in rids]

    assert serve(16) == serve(0)


@pytest.mark.slow
def test_chunked_prefill_admission_matches_generate_llama():
    """The chunked path is model-generic (RoPE positions and the GQA int8
    cache follow the chunk's global offsets)."""
    import dataclasses

    from dsml_tpu.models.llama import Llama, LlamaConfig

    model = Llama(dataclasses.replace(LlamaConfig.tiny(), kv_quant=True))
    cfg = model.config
    params = model.init(14)
    prompts = _prompts(cfg, [7, 41, 12], seed=14)
    srv = ContinuousBatcher(model, params, n_slots=2, prompt_buckets=(16, 64),
                            prefill_chunk=16)
    rids = [srv.submit(p, 5) for p in prompts]
    out = srv.run()
    for rid, prompt in zip(rids, prompts):
        assert out[rid] == _reference(model, params, prompt, 5), rid


def test_decode_continues_between_chunks_of_long_admission():
    """THE head-of-line fix (VERDICT r3 item 2): while a long prompt's
    admission is mid-flight, every scheduler tick still decodes the active
    slots — tokens keep flowing between the admission's chunks instead of
    stalling for the whole prefill."""
    cfg = GPT2Config.tiny()
    model = GPT2(cfg)
    params = model.init(15)
    short, long = _prompts(cfg, [5, 100], seed=15)

    srv = ContinuousBatcher(model, params, n_slots=2, prompt_buckets=(8, 128),
                            prefill_chunk=16)
    rid_short = srv.submit(short, 40)
    srv.step()  # short admitted + starts decoding
    assert srv.n_active == 1
    rid_long = srv.submit(long, 4)  # 100 tokens → 7 chunks of 16

    chunk_ticks = 0  # ticks that ran with the long admission still pending
    while srv.n_pending or srv.n_queued:
        before = len(srv._live[rid_short].tokens)
        srv.step()
        if srv.n_pending:
            chunk_ticks += 1
            # the short request decoded DURING the long prompt's admission
            assert len(srv._live[rid_short].tokens) == before + 1
    # the admission genuinely spanned multiple ticks (7 chunks → >= 6
    # pending-observed ticks), so the assertion above had real coverage
    assert chunk_ticks >= 5
    out = srv.run()
    assert out[rid_short] == _reference(model, params, short, 40)
    assert out[rid_long] == _reference(model, params, long, 4)


def test_chunked_submit_skips_bucket_limit():
    """With chunking on, prompts longer than the largest bucket are legal
    (the chunk grid, not the bucket table, bounds admission); the bucket
    check still applies when chunking is off."""
    cfg = GPT2Config.tiny()
    model = GPT2(cfg)
    params = model.init(16)
    srv = ContinuousBatcher(model, params, n_slots=1, prompt_buckets=(16,),
                            prefill_chunk=16)
    rid = srv.submit(np.zeros(64, np.int32), 2)  # > largest bucket: OK
    out = srv.run()
    assert len(out[rid]) == 2
    with pytest.raises(ValueError, match="exceeds max_seq"):
        srv.submit(np.zeros(cfg.max_seq, np.int32), 1)


def test_prompt_buckets_sorted_and_deduped():
    """An unsorted/duplicated bucket tuple must not admit short prompts
    into the largest bucket — the constructor normalizes it."""
    cfg = GPT2Config.tiny()
    model = GPT2(cfg)
    srv = ContinuousBatcher(model, model.init(0), n_slots=1,
                            prompt_buckets=(64, 8, 64, 32))
    assert srv.prompt_buckets == (8, 32, 64)


def test_speculative_batcher_small_default():
    """Default-suite representative of the speculative batcher: one serve
    with drafts on vs off, token-identical (the staggered-arrival × EOS ×
    chunked-prefill matrix runs under -m slow)."""
    cfg = GPT2Config.tiny()
    model = GPT2(cfg)
    params = model.init(17)
    prompts = _prompts(cfg, [5, 17], seed=17)

    def serve(**kw):
        srv = ContinuousBatcher(model, params, n_slots=2, prompt_buckets=(32,), **kw)
        rids = [srv.submit(p, 8) for p in prompts]
        out = srv.run()
        return [out[r] for r in rids]

    assert serve(speculative_window=5) == serve()


@pytest.mark.slow
def test_speculative_batcher_matches_plain_and_generate():
    """speculative_window is pure throughput: per-slot prompt-lookup
    drafts + one multi-query verify per tick commit EXACTLY the tokens
    the plain batcher (and standalone generate) produce — across
    staggered arrivals, mid-window EOS retirement, and composition with
    chunked-prefill admission."""
    cfg = GPT2Config.tiny()
    model = GPT2(cfg)
    params = model.init(17)
    prompts = _prompts(cfg, [5, 17, 32, 9, 26], seed=17)
    budgets = [6, 3, 8, 5, 4]

    def serve(**kw):
        srv = ContinuousBatcher(model, params, n_slots=2,
                                prompt_buckets=(8, 16, 32), **kw)
        rids = [srv.submit(p, n) for p, n in zip(prompts[:3], budgets[:3])]
        srv.step()
        rids += [srv.submit(p, n) for p, n in zip(prompts[3:], budgets[3:])]
        out = srv.run()
        return [out[r] for r in rids]

    plain = serve()
    assert serve(speculative_window=5) == plain
    assert serve(speculative_window=5, prefill_chunk=8) == plain
    for tokens, p, n in zip(plain, prompts, budgets):
        assert tokens == _reference(model, params, p, n)

    # EOS retirement mid-window: a slot must stop AT the eos even when the
    # verify window would have committed more
    ref = _reference(model, params, prompts[0], 6)
    eos = ref[2]
    srv = ContinuousBatcher(model, params, n_slots=1, eos_id=eos,
                            prompt_buckets=(8,), speculative_window=5)
    rid = srv.submit(prompts[0], 6)
    out = srv.run()
    assert out[rid] == ref[: ref.index(eos) + 1]


def test_latency_stats_track_requests():
    """TTFT/ITL/e2e percentiles accumulate per retired request, warmups
    can be reset out, and the invariants hold (ttft <= e2e; itl present
    only for multi-token requests)."""
    cfg = GPT2Config.tiny()
    model = GPT2(cfg)
    params = model.init(19)
    srv = ContinuousBatcher(model, params, n_slots=2, prompt_buckets=(16,))
    assert srv.latency_stats() == {"n_requests": 0}
    srv.submit(_prompts(cfg, [5], seed=19)[0], 3)
    srv.run()
    srv.reset_latency_stats()
    assert srv.latency_stats() == {"n_requests": 0}

    for p, n in zip(_prompts(cfg, [5, 9, 7], seed=20), (4, 1, 6)):
        srv.submit(p, n)
    srv.run()
    stats = srv.latency_stats()
    assert stats["n_requests"] == 3
    assert 0 < stats["ttft_p50_s"] <= stats["e2e_p50_s"]
    assert stats["ttft_p99_s"] <= stats["e2e_p99_s"]
    # two of three requests decoded past their first emission → gap samples
    assert stats["gap_p50_s"] > 0 and stats["gap_p99_s"] >= stats["gap_p50_s"]


@pytest.mark.slow
def test_prefix_cache_tokens_identical_and_prefill_work_drops():
    """register_prefix: prompts sharing a registered head admit by copying
    the stored rows and chunk-prefilling only the suffix — tokens equal
    the uncached batcher AND standalone generate, while admission chunk
    calls drop by the shared-prefix work. Covers suffix admissions, an
    exact-prefix prompt (zero prefill work), an unrelated prompt, and
    longest-match among two registered prefixes."""
    cfg = GPT2Config.tiny()
    model = GPT2(cfg)
    params = model.init(25)
    rng = np.random.default_rng(25)
    system = rng.integers(0, cfg.vocab_size, (40,)).astype(np.int32)
    longer = np.concatenate([system, rng.integers(0, cfg.vocab_size, (16,)).astype(np.int32)])
    prompts = [
        np.concatenate([system, rng.integers(0, cfg.vocab_size, (l,)).astype(np.int32)])
        for l in (5, 20)
    ] + [
        np.concatenate([longer, rng.integers(0, cfg.vocab_size, (4,)).astype(np.int32)]),
        rng.integers(0, cfg.vocab_size, (12,)).astype(np.int32),  # unrelated
        system.copy(),  # exactly the prefix
    ]
    budgets = [6, 4, 5, 7, 3]

    def serve(register):
        srv = ContinuousBatcher(model, params, n_slots=2,
                                prompt_buckets=(64, 128), prefill_chunk=16)
        calls = [0]
        orig = srv._prefill_chunk

        def counting(*a, **k):
            calls[0] += 1
            return orig(*a, **k)

        srv._prefill_chunk = counting
        for p in register:
            srv.register_prefix(p)
        setup = calls[0]
        rids = [srv.submit(p, n) for p, n in zip(prompts, budgets)]
        out = srv.run()
        return [out[r] for r in rids], calls[0] - setup

    plain, n_plain = serve([])
    cached, n_cached = serve([system, longer])
    assert cached == plain
    assert n_cached < n_plain  # the shared-head prefill work disappeared
    for toks, p, n in zip(cached, prompts, budgets):
        assert toks == _reference(model, params, p, n)


def test_prefix_cache_validation():
    cfg = GPT2Config.tiny()
    model = GPT2(cfg)
    srv = ContinuousBatcher(model, model.init(0), prompt_buckets=(16,))
    with pytest.raises(ValueError, match="prefill_chunk"):
        srv.register_prefix(np.zeros(4, np.int32))
    srv2 = ContinuousBatcher(model, model.init(0), prompt_buckets=(16,),
                             prefill_chunk=16)
    with pytest.raises(ValueError, match="empty"):
        srv2.register_prefix(np.zeros(0, np.int32))


def test_speculative_batcher_validation():
    cfg = GPT2Config.tiny()
    model = GPT2(cfg)
    params = model.init(0)
    with pytest.raises(ValueError, match="greedy-only"):
        ContinuousBatcher(model, params, temperature=0.5, speculative_window=4)
    with pytest.raises(ValueError, match="decode_quantum"):
        ContinuousBatcher(model, params, decode_quantum=2, speculative_window=4)
    with pytest.raises(ValueError, match="speculative_window"):
        ContinuousBatcher(model, params, speculative_window=1)
    srv = ContinuousBatcher(model, params, speculative_window=8)
    with pytest.raises(ValueError, match="speculative_window"):
        # window rows of a just-finishing request would escape the cache
        srv.submit(np.zeros(64, np.int32), cfg.max_seq - 64 - 2)


@pytest.mark.slow
def test_speculative_batcher_llama_and_tp(devices8):
    """Speculative serving is model-generic (Llama GQA + RoPE at per-slot
    window offsets) and TP composes (shard_map verify with the
    head-sharded cache) — tokens equal the plain batcher."""
    from dsml_tpu.models.llama import Llama, LlamaConfig
    from dsml_tpu.parallel.mesh import MeshSpec, build_mesh

    model = Llama(LlamaConfig.tiny())
    cfg = model.config
    params = model.init(18)
    prompts = _prompts(cfg, [7, 21, 12], seed=18)

    def serve(**kw):
        srv = ContinuousBatcher(model, params, n_slots=2,
                                prompt_buckets=(8, 32), **kw)
        rids = [srv.submit(p, 6) for p in prompts]
        out = srv.run()
        return [out[r] for r in rids]

    plain = serve()
    assert serve(speculative_window=4) == plain
    mesh = build_mesh(MeshSpec(tp=2), devices8[:2])
    assert serve(speculative_window=4, mesh=mesh) == plain


def test_submit_validation():
    cfg = GPT2Config.tiny()
    model = GPT2(cfg)
    srv = ContinuousBatcher(model, model.init(0), n_slots=1, prompt_buckets=(16,))
    with pytest.raises(ValueError, match="exceeds max_seq"):
        srv.submit(np.zeros(100, np.int32), cfg.max_seq)
    with pytest.raises(ValueError, match="empty"):
        srv.submit(np.zeros(0, np.int32), 4)
    with pytest.raises(ValueError, match="exceeds the largest bucket"):
        srv.submit(np.zeros(64, np.int32), 4)  # > largest bucket (16)
    with pytest.raises(ValueError, match="max_new_tokens"):
        srv.submit(np.zeros(4, np.int32), 0)  # generate rejects this too


def test_budget_one_requests_drain_through_one_slot():
    """Requests that finish AT prefill never occupy the slot: a single slot
    admits the whole queue in one pass, and collect() drains (a second
    round reports only its own requests)."""
    cfg = GPT2Config.tiny()
    model = GPT2(cfg)
    params = model.init(5)
    srv = ContinuousBatcher(model, params, n_slots=1, prompt_buckets=(8,))
    prompts = _prompts(cfg, [4, 5, 6], seed=5)
    rids = [srv.submit(p, 1) for p in prompts]
    srv.step()  # one admission pass serves all three budget-1 requests
    out = srv.collect()
    assert set(out) == set(rids) and all(len(t) == 1 for t in out.values())
    for rid, p in zip(rids, prompts):
        assert out[rid] == _reference(model, params, p, 1)
    # second round: collect() reports only the new request
    rid2 = srv.submit(prompts[0], 2)
    out2 = srv.run()
    assert set(out2) == {rid2}


def test_turbo_factor_tokens_identical_and_engages():
    """turbo_factor is pure dispatch amortization: greedy AND sampled
    tokens equal the plain batcher's (and therefore generate's), and the
    escalated program actually engages once the queue drains and every
    active request holds the turbo budget (counter-pinned)."""
    cfg = GPT2Config.tiny()
    model = GPT2(cfg)
    params = model.init(3)
    prompts = _prompts(cfg, [5, 9, 7], seed=3)
    # the middle request retires first; the queued third then admits with a
    # large budget, so once the queue drains every active request still
    # holds >= the turbo quantum (6) and the escalation engages
    budgets = [24, 10, 22]

    def serve(turbo, temperature=0.0):
        srv = ContinuousBatcher(model, params, n_slots=2,
                                temperature=temperature, prompt_buckets=(16,),
                                decode_quantum=2, turbo_factor=turbo)
        rids = [srv.submit(p, n) for p, n in zip(prompts, budgets)]
        out = srv.run()
        return [out[r] for r in rids], srv

    base, srv0 = serve(0)
    turbo, srv1 = serve(3)
    assert base == turbo
    assert srv0.n_turbo_ticks == 0 and srv1.n_turbo_ticks > 0
    # and the turbo run used strictly fewer decode dispatches
    assert (srv1.n_turbo_ticks + srv1.n_plain_ticks) < srv0.n_plain_ticks

    sb, _ = serve(0, temperature=0.9)
    st, srv2 = serve(3, temperature=0.9)
    assert sb == st and srv2.n_turbo_ticks > 0


@pytest.mark.slow
def test_turbo_respects_eos_and_admissions():
    """An EOS mid-turbo retires the request exactly where the plain
    batcher would (the sampled stream makes the tokens non-degenerate —
    tiny-model greedy collapses to one repeated token); while a request
    waits in the queue the turbo program never runs (admission cadence
    keeps the base quantum)."""
    cfg = GPT2Config.tiny()
    model = GPT2(cfg)
    params = model.init(2)
    prompt = _prompts(cfg, [6], seed=2)[0]

    def serve(turbo, eos=None):
        srv = ContinuousBatcher(model, params, n_slots=1, eos_id=eos,
                                temperature=0.8, seed=7, prompt_buckets=(8,),
                                decode_quantum=1, turbo_factor=turbo)
        a = srv.submit(prompt, 12)
        b = srv.submit(prompt, 12)  # queued behind the single slot
        out = srv.run()
        return out[a], out[b], srv

    ra, rb, _ = serve(0)
    # rid0 decodes under PLAIN ticks (rid1 waits in the queue, which gates
    # turbo off); rid1 runs alone afterwards, all-turbo. Draw the eos from
    # rid1's OWN stream at an index inside its second turbo quantum
    # (emissions: prefill tok 0, turbo ticks decode 1-4, 5-8, ...) so the
    # truncated-tail discard path of a turbo tick is what retires it.
    eos = rb[6]
    assert eos not in rb[:6]  # really retires at index 6, mid-quantum
    pa, pb, s0 = serve(0, eos)
    ta, tb, s1 = serve(4, eos)
    assert (pa, pb) == (ta, tb)
    assert len(pb) == 7 and pb[-1] == eos  # truncated at the mid-turbo eos
    assert s0.n_turbo_ticks == 0 and s1.n_turbo_ticks > 0


def test_turbo_factor_validation():
    cfg = GPT2Config.tiny()
    model = GPT2(cfg)
    params = model.init(0)
    with pytest.raises(ValueError, match="turbo_factor"):
        ContinuousBatcher(model, params, turbo_factor=1)
    with pytest.raises(ValueError, match="speculative"):
        ContinuousBatcher(model, params, turbo_factor=2, speculative_window=4)
    with pytest.raises(ValueError, match="max_seq"):
        ContinuousBatcher(model, params, decode_quantum=cfg.max_seq,
                          turbo_factor=2)


@pytest.mark.slow
def test_moe_model_through_batcher():
    """A MoE config (top-2 of 4 experts) rides the same slot-decode path:
    batcher tokens equal standalone generate, with turbo escalation on —
    the scheduler is model-architecture-agnostic."""
    import dataclasses

    cfg = dataclasses.replace(GPT2Config.tiny(), n_experts=4, expert_top_k=2)
    model = GPT2(cfg)
    params = model.init(0)
    prompts = _prompts(cfg, [5, 9], seed=0)
    srv = ContinuousBatcher(model, params, n_slots=2, prompt_buckets=(16,),
                            decode_quantum=2, turbo_factor=2)
    rids = [srv.submit(p, 8) for p in prompts]
    out = srv.run()
    for rid, p in zip(rids, prompts):
        assert out[rid] == _reference(model, params, p, 8), rid
    assert srv.n_turbo_ticks > 0


def test_prefix_cache_small_default():
    """Default-lane functional pin for register_prefix (the heavy
    identity-and-work-accounting matrix runs under -m slow): a request
    whose prompt extends a registered prefix decodes the same tokens as an
    uncached batcher, and an exact-prefix prompt admits with zero prefill
    work."""
    cfg = GPT2Config.tiny()
    model = GPT2(cfg)
    params = model.init(9)
    prefix = _prompts(cfg, [8], seed=9)[0]
    suffix = _prompts(cfg, [4], seed=10)[0]
    full = np.concatenate([prefix, suffix])

    def serve(register):
        srv = ContinuousBatcher(model, params, n_slots=1, prompt_buckets=(16,),
                                prefill_chunk=4)
        if register:
            srv.register_prefix(prefix)
        a = srv.submit(full, 4)
        b = srv.submit(prefix, 3)  # exact-prefix admission
        out = srv.run()
        return out[a], out[b]

    assert serve(True) == serve(False)
    # and both match standalone generate
    ra, rb = serve(True)
    assert ra == _reference(model, params, full, 4)
    assert rb == _reference(model, params, prefix, 3)


@pytest.mark.slow
def test_llama_kvquant_turbo_composition_matches_generate():
    """One composition end to end:
    Llama family + GQA + int8 KV cache + turbo escalation — tokens equal
    standalone generate and turbo genuinely engages."""
    import dataclasses

    from dsml_tpu.models.llama import Llama, LlamaConfig

    cfg = dataclasses.replace(LlamaConfig.tiny(), max_seq=256, kv_quant=True)
    model = Llama(cfg)
    params = model.init(11)
    prompts = _prompts(cfg, [6, 14], seed=11)
    srv = ContinuousBatcher(model, params, n_slots=2, prompt_buckets=(16,),
                            decode_quantum=2, turbo_factor=3)
    rids = [srv.submit(p, 14) for p in prompts]
    out = srv.run()
    for rid, p in zip(rids, prompts):
        assert out[rid] == _reference(model, params, p, 14), rid
    assert srv.n_turbo_ticks > 0


def test_queue_cap_sheds_explicitly():
    """max_queue: overload becomes an explicit QueueFull + a
    serving_shed_total count instead of an unbounded queue — and shed
    requests leave the admitted ones untouched (they still drain with
    reference-identical tokens)."""
    from dsml_tpu import obs
    from dsml_tpu.serving import QueueFull

    cfg = GPT2Config.tiny()
    model = GPT2(cfg)
    params = model.init(0)
    srv = ContinuousBatcher(model, params, n_slots=1, max_queue=2)
    prompts = _prompts(cfg, [5, 6, 7, 8])
    obs.enable(forensics=False)
    try:
        reg = obs.get_registry()
        shed = reg.counter(
            "serving_shed_total",
            "requests rejected by the queue cap",
            labels=("replica", "role"),
        )
        before = shed.value(replica="0", role="decode")
        rids = [srv.submit(p, 3) for p in prompts[:2]]  # queue holds 2
        with pytest.raises(QueueFull, match="cap"):
            srv.submit(prompts[2], 3)
        assert shed.value(replica="0", role="decode") - before == 1
        assert srv.n_queued == 2  # the shed request left no residue
        # draining frees queue space: submit succeeds again afterwards
        out = srv.run()
        rids.append(srv.submit(prompts[3], 3))
        out.update(srv.run())
        for rid, p in zip(rids, [prompts[0], prompts[1], prompts[3]]):
            assert out[rid] == _reference(model, params, p, 3)
    finally:
        obs.disable()


def test_queue_cap_validation_and_default_unbounded():
    cfg = GPT2Config.tiny()
    model = GPT2(cfg)
    params = model.init(0)
    with pytest.raises(ValueError, match="max_queue"):
        ContinuousBatcher(model, params, max_queue=-1)
    srv = ContinuousBatcher(model, params, n_slots=1)  # default: unbounded
    for p in _prompts(cfg, [4] * 12):
        srv.submit(p, 2)
    assert srv.n_queued == 12


def test_abandon_evacuates_unfinished_requests():
    """abandon() returns every queued + active request (the replica-failure
    evacuation) and resets the scheduler; finished results stay
    collectable, and the batcher serves fresh work afterwards."""
    cfg = GPT2Config.tiny()
    model = GPT2(cfg)
    params = model.init(0)
    srv = ContinuousBatcher(model, params, n_slots=2)
    prompts = _prompts(cfg, [5, 6, 7])
    done_rid = srv.submit(prompts[0], 1)   # retires at prefill
    live_rids = [srv.submit(prompts[1], 8), srv.submit(prompts[2], 8)]
    srv.step()  # admits everything; budget-1 request already retired
    evacuated = srv.abandon()
    assert sorted(r.rid for r in evacuated) == sorted(live_rids)
    assert srv.n_active == 0 and srv.n_queued == 0 and srv.n_pending == 0
    assert done_rid in srv.collect()  # finished work survives the evacuation
    # the reset batcher still serves correctly (cache garbage overwritten)
    rid = srv.submit(prompts[1], 4)
    assert srv.run()[rid] == _reference(model, params, prompts[1], 4)

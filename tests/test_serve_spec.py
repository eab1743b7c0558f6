"""Speculative decoding (r19): token identity is the whole contract.

A speculative engine may only change WHEN tokens are computed (K drafts
scored in one batched verify forward), never WHICH tokens come out: greedy
output with speculation on must be bit-identical to the unsped engine —
across page-boundary crossings, eviction/recompute, prefix-cache hits and
the prefill/decode disaggregation handoff. The same bar applies to
scan_layers checkpoints served with a stacked cache carry: they must match
the training forward's greedy argmax.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from pytorch_distributed_training_example_tpu.models import registry
from pytorch_distributed_training_example_tpu.serve import (
    engine as engine_lib, kv_cache, spec_decode)


def _model(name="llama_tiny", seq_len=128, **kw):
    bundle = registry.create_model(name, seq_len=seq_len,
                                   dtype=jnp.float32,
                                   param_dtype=jnp.float32, **kw)
    module = bundle.module
    params = module.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32),
                         train=False)["params"]
    return module, params


def _requests(module, n, seed, plen_lo=5, plen_hi=30, new_lo=8, new_hi=40):
    rng = np.random.default_rng(seed)
    reqs = []
    for rid in range(n):
        plen = int(rng.integers(plen_lo, plen_hi))
        prompt = rng.integers(0, module.vocab_size, size=plen).tolist()
        reqs.append(engine_lib.Request(
            request_id=f"r{rid}", prompt=prompt,
            max_new_tokens=int(rng.integers(new_lo, new_hi))))
    return reqs


def _drain(eng):
    while eng.has_work:
        eng.step()
    return {r.request_id: r.generated for r in eng.completed}


def _run_engine(module, params, spec, *, spec_decode_=None, draft_len=4,
                n_req=6, seed=0, plen_lo=5, plen_hi=30, new_lo=8, new_hi=40,
                **kw):
    eng = engine_lib.ContinuousBatchingEngine(
        module, params, spec, spec_decode=spec_decode_, draft_len=draft_len,
        decode_buckets=(1, 2, 4), prompt_buckets=(16, 32),
        max_model_len=96, **kw)
    warm = eng.warmup()
    for req in _requests(module, n_req, seed, plen_lo, plen_hi,
                         new_lo, new_hi):
        eng.submit(req)
    out = _drain(eng)
    return eng, warm, out


def _jit_greedy(module, params, prompt, steps):
    """Greedy continuation via the COMPILED training forward. The oracle
    must be jitted like the engine's programs: eager op-by-op execution
    materializes bf16/fp32 intermediates XLA would fuse, and that sub-ulp
    skew can flip argmax at near-ties — a harness artifact, not an engine
    difference."""
    fwd = jax.jit(lambda t: module.apply({"params": params}, t, train=False))
    toks = list(prompt)
    out = []
    for _ in range(steps):
        logits = fwd(jnp.asarray([toks], jnp.int32))
        out.append(int(jnp.argmax(logits[0, len(toks) - 1])))
        toks.append(out[-1])
    return out


# ---------------------------------------------------------------------------
# NGramProposer unit behavior
# ---------------------------------------------------------------------------


def test_ngram_proposer_matches_repeats_and_respects_budget():
    prop = spec_decode.NGramProposer(draft_len=4)
    # trailing 3-gram [7, 8, 9] occurred earlier, followed by [1, 2, 3, 4]
    ctx = [7, 8, 9, 1, 2, 3, 4, 5, 7, 8, 9]
    assert prop._match(ctx, 4) == [1, 2, 3, 4]
    assert prop._match(ctx, 2) == [1, 2]       # budget clamps the copy
    assert prop._match(ctx, 0) == []
    assert prop._match([1, 2, 3], 4) == []     # no earlier occurrence
    # most RECENT earlier occurrence wins over an older one
    ctx2 = [5, 6, 1, 5, 6, 2, 5, 6]
    assert prop._match(ctx2, 1) == [2]


def test_ngram_proposer_rejects_bad_config():
    with pytest.raises(ValueError):
        spec_decode.NGramProposer(draft_len=4, max_ngram=1, min_ngram=2)


# ---------------------------------------------------------------------------
# token identity: speculation on == speculation off, bit for bit
# ---------------------------------------------------------------------------


def test_spec_ngram_token_identity_with_page_crossings(devices):
    module, params = _model()
    spec = engine_lib.spec_for_module(module, num_pages=64, page_size=8)
    _, _, base = _run_engine(module, params, spec)
    eng, _, sped = _run_engine(module, params, spec, spec_decode_="ngram")
    assert sped == base
    st = eng.stats
    assert st["spec_steps"] > 0
    assert 0 <= st["accepted_tokens"] <= st["draft_tokens"]
    hist = sum(st[f"spec_accept_{n}"] for n in range(5))
    assert hist > 0 and st["accepted_tokens"] == sum(
        n * st[f"spec_accept_{n}"] for n in range(5))


def test_spec_draft_model_token_identity_and_self_draft_acceptance(devices):
    module, params = _model()
    spec = engine_lib.spec_for_module(module, num_pages=64, page_size=8)
    _, _, base = _run_engine(module, params, spec)
    # Self-drafting with the TARGET model: every draft is the target's own
    # argmax, so the verify must accept all of them — any rejection would
    # mean the draft catch-up programs diverge from the target decode.
    prop = spec_decode.DraftModelProposer(module, params, draft_len=4)
    eng, _, sped = _run_engine(module, params, spec, spec_decode_=prop)
    assert sped == base
    st = eng.stats
    assert st["draft_tokens"] > 0
    assert st["accepted_tokens"] == st["draft_tokens"]


def test_spec_token_identity_under_eviction(devices):
    module, params = _model()
    # Starve the pool so decode-time page growth forces evictions.
    spec = engine_lib.spec_for_module(module, num_pages=20, page_size=8)
    kw = dict(n_req=5, seed=3, plen_lo=20, plen_hi=30, new_lo=30, new_hi=50)
    a, _, base = _run_engine(module, params, spec, **kw)
    b, _, sped = _run_engine(module, params, spec, spec_decode_="ngram", **kw)
    assert b.stats["evictions"] > 0
    assert sped == base


def test_spec_token_identity_with_prefix_cache(devices):
    module, params = _model()
    spec = engine_lib.spec_for_module(module, num_pages=96, page_size=8)
    rng = np.random.default_rng(11)
    shared = rng.integers(0, module.vocab_size, size=16).tolist()

    def submit_all(eng):
        eng.warmup()
        for rid in range(5):
            tail = rng.integers(0, module.vocab_size,
                                size=int(rng.integers(4, 12))).tolist()
            eng.submit(engine_lib.Request(
                request_id=f"r{rid}", prompt=shared + tail,
                max_new_tokens=int(rng.integers(10, 30))))
        return _drain(eng)

    kw = dict(decode_buckets=(1, 2, 4), prompt_buckets=(16, 32),
              max_model_len=96, prefix_cache=True)
    rng = np.random.default_rng(11)
    shared = rng.integers(0, module.vocab_size, size=16).tolist()
    base = submit_all(engine_lib.ContinuousBatchingEngine(
        module, params, spec, **kw))
    rng = np.random.default_rng(11)
    shared = rng.integers(0, module.vocab_size, size=16).tolist()
    eng = engine_lib.ContinuousBatchingEngine(
        module, params, spec, spec_decode="ngram", **kw)
    sped = submit_all(eng)
    assert eng.stats["cached_tokens"] > 0  # the prefix cache actually hit
    assert sped == base


def test_spec_token_identity_through_disagg_handoff(devices):
    module, params = _model()

    def pair(spec_decode_):
        kw = dict(decode_buckets=(1, 2, 4), prompt_buckets=(16, 32),
                  max_model_len=96)
        spec_p = engine_lib.spec_for_module(module, num_pages=48, page_size=8)
        spec_d = engine_lib.spec_for_module(module, num_pages=48, page_size=8)
        return engine_lib.DisaggregatedServe(
            engine_lib.ContinuousBatchingEngine(
                module, params, spec_p, role="prefill", **kw),
            engine_lib.ContinuousBatchingEngine(
                module, params, spec_d, role="decode",
                spec_decode=spec_decode_, **kw))

    base = pair(None)
    base.warmup()
    for req in _requests(module, 5, 4):
        base.submit(req)
    base_out = {r.request_id: r.generated for r in base.run()}

    sped = pair("ngram")
    sped.warmup()
    for req in _requests(module, 5, 4):
        sped.submit(req)
    sped_out = {r.request_id: r.generated for r in sped.run()}
    assert sped.stats["handoffs_out"] > 0
    assert sped.stats["spec_steps"] > 0
    assert sped_out == base_out


def test_prefill_role_engine_never_speculates(devices):
    module, params = _model()
    spec = engine_lib.spec_for_module(module, num_pages=32, page_size=8)
    eng = engine_lib.ContinuousBatchingEngine(
        module, params, spec, role="prefill", spec_decode="ngram",
        decode_buckets=(1, 2), prompt_buckets=(16, 32), max_model_len=96)
    assert eng.proposer is None


def test_spec_rejects_unknown_mode(devices):
    module, params = _model()
    spec = engine_lib.spec_for_module(module, num_pages=32, page_size=8)
    with pytest.raises(ValueError):
        engine_lib.ContinuousBatchingEngine(
            module, params, spec, spec_decode="nope",
            decode_buckets=(1, 2), prompt_buckets=(16, 32))
    with pytest.raises(ValueError):
        engine_lib.ContinuousBatchingEngine(
            module, params, spec, spec_decode="ngram", draft_len=0,
            decode_buckets=(1, 2), prompt_buckets=(16, 32))


# ---------------------------------------------------------------------------
# compile discipline: verify programs are warmed, steady state stays flat
# ---------------------------------------------------------------------------


def test_spec_no_steady_state_recompile(devices):
    module, params = _model()
    spec = engine_lib.spec_for_module(module, num_pages=64, page_size=8)
    eng = engine_lib.ContinuousBatchingEngine(
        module, params, spec, spec_decode="ngram", draft_len=4,
        decode_buckets=(1, 2, 4), prompt_buckets=(16, 32), max_model_len=96)
    n = eng.warmup()
    # decode(3) + prefill(2) + verify(3 batch buckets x 3 draft buckets)
    assert n == 3 + 2 + 9
    assert eng.stats["compiles"] == n
    for req in _requests(module, 6, 0):
        eng.submit(req)
    _drain(eng)
    assert eng.stats["compiles"] == n, "speculation recompiled in steady state"


def test_spec_draft_model_no_steady_state_recompile(devices):
    module, params = _model()
    spec = engine_lib.spec_for_module(module, num_pages=64, page_size=8)
    prop = spec_decode.DraftModelProposer(module, params, draft_len=4)
    eng = engine_lib.ContinuousBatchingEngine(
        module, params, spec, spec_decode=prop, draft_len=4,
        decode_buckets=(1, 2, 4), prompt_buckets=(16, 32), max_model_len=96)
    n = eng.warmup()
    assert eng.stats["compiles"] == n
    for req in _requests(module, 6, 0):
        eng.submit(req)
    _drain(eng)
    assert eng.stats["compiles"] == n, "draft proposer recompiled mid-run"


def test_spec_rollback_returns_overshoot_pages(devices):
    module, params = _model()
    spec = engine_lib.spec_for_module(module, num_pages=64, page_size=8)
    eng, _, _ = _run_engine(module, params, spec, spec_decode_="ngram")
    # Every request retired; every page (minus the reserved scratch page)
    # must be back in the pool — rollback may not leak overshoot pages.
    assert eng.pool.num_free == spec.num_pages - kv_cache.RESERVED_PAGES


# ---------------------------------------------------------------------------
# scan_layers decode: stacked cache carry == unrolled == training forward
# ---------------------------------------------------------------------------


def test_scan_layers_decode_parity_and_stacked_cache(devices):
    module, params = _model()
    scanned = module.copy(scan_layers=True)
    # Scanned params are stacked [L, ...]; restack the unrolled init so both
    # engines serve identical weights.
    stacked = {"blocks": {"block": jax.tree.map(
        lambda *xs: jnp.stack(xs),
        *(params[f"block_{i}"] for i in range(module.num_layers)))}}
    sparams = {**{k: v for k, v in params.items()
                  if not k.startswith("block_")}, **stacked}
    spec = engine_lib.spec_for_module(scanned, num_pages=64, page_size=8)
    eng = engine_lib.ContinuousBatchingEngine(
        scanned, sparams, spec, decode_buckets=(1, 2), prompt_buckets=(16,),
        max_model_len=64)
    # The cache pytree is ONE stacked [L, P, page_size, Hkv, D] carry per
    # K/V pool, not per-layer leaves.
    leaves = jax.tree.leaves(eng.cache)
    assert len(leaves) == 2
    assert all(leaf.shape[0] == module.num_layers and leaf.ndim == 5
               for leaf in leaves)
    eng.warmup()
    for req in _requests(scanned, 3, 5, plen_hi=14, new_hi=20):
        eng.submit(req)
    _drain(eng)
    for r in eng.completed:
        ref = _jit_greedy(scanned, sparams, r.prompt, len(r.generated))
        assert r.generated == ref, r.request_id


def test_scan_layers_spec_decode_token_identity(devices):
    module, params = _model()
    scanned = module.copy(scan_layers=True)
    stacked = {"blocks": {"block": jax.tree.map(
        lambda *xs: jnp.stack(xs),
        *(params[f"block_{i}"] for i in range(module.num_layers)))}}
    sparams = {**{k: v for k, v in params.items()
                  if not k.startswith("block_")}, **stacked}
    spec = engine_lib.spec_for_module(scanned, num_pages=64, page_size=8)
    _, _, base = _run_engine(scanned, sparams, spec, n_req=4, seed=6)
    eng, _, sped = _run_engine(scanned, sparams, spec, spec_decode_="ngram",
                               n_req=4, seed=6)
    assert sped == base
    assert eng.stats["spec_steps"] > 0

"""Continuous-batching engine: scheduler unit tests (admission order,
slot reuse after eviction, bucket selection) and end-to-end exact token
parity with ``greedy_generate``."""
import collections

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.launch.engine import (ContinuousBatchingEngine, EngineConfig,
                                 Request, default_buckets, pick_bucket)
from repro.models import model as M
from repro.train.step import greedy_generate

# engine ticks advance on a virtual clock fed by wall time; unit tests
# freeze it so scheduling decisions are deterministic w.r.t. arrivals
_FROZEN = lambda: 0.0  # noqa: E731


def _cfg_params():
    cfg = configs.get("smollm_135m", smoke=True)
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _prompt(i, length, vocab):
    p = jax.random.randint(jax.random.fold_in(jax.random.PRNGKey(7), i),
                           (length,), 0, vocab)
    return tuple(int(t) for t in np.asarray(p))


# --------------------------------------------------------------- buckets

def test_bucket_selection():
    assert default_buckets(64) == (8, 16, 32, 64)
    assert default_buckets(48) == (8, 16, 32, 48)
    buckets = (8, 16, 32)
    assert pick_bucket(1, buckets) == 8
    assert pick_bucket(8, buckets) == 8
    assert pick_bucket(9, buckets) == 16
    assert pick_bucket(32, buckets) == 32
    with pytest.raises(ValueError):
        pick_bucket(33, buckets)


def test_submit_rejects_oversized():
    cfg, params = _cfg_params()
    eng = ContinuousBatchingEngine(cfg, params,
                                   EngineConfig(n_slots=2, max_ctx=16,
                                                backend="reference"))
    eng.submit(Request(rid=0, prompt=_prompt(0, 8, cfg.vocab_size),
                       max_new_tokens=8))          # 8 + 8 - 1 = 15 fits
    with pytest.raises(ValueError):
        eng.submit(Request(rid=1, prompt=_prompt(1, 8, cfg.vocab_size),
                           max_new_tokens=10))     # last pos 17 > 16


def test_ring_cache_requires_window_sized_ctx():
    """attn_local's prefill ring is always `window` wide; an engine whose
    slot cache is narrower must fail loudly at construction, not with a
    shape error inside insert_cache_slot."""
    cfg = configs.get("recurrentgemma_2b", smoke=True)   # window = 16
    with pytest.raises(ValueError, match="window"):
        ContinuousBatchingEngine(cfg, params=None,
                                 ecfg=EngineConfig(n_slots=2, max_ctx=8))


def test_frontend_arch_requires_embeddings():
    cfg = configs.get("internvl2_76b", smoke=True)
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    eng = ContinuousBatchingEngine(
        cfg, params, EngineConfig(n_slots=2, max_ctx=cfg.frontend_len + 16,
                                  backend="reference"))
    with pytest.raises(ValueError, match="frontend"):
        eng.submit(Request(rid=0, prompt=_prompt(0, 4, cfg.vocab_size),
                           max_new_tokens=2))


# ------------------------------------------------------------- scheduler

def test_admission_order_fifo_by_arrival():
    """With one slot, requests must be served in arrival order even when
    submitted shuffled."""
    cfg, params = _cfg_params()
    eng = ContinuousBatchingEngine(
        cfg, params,
        EngineConfig(n_slots=1, max_ctx=16, backend="reference"),
        time_fn=_FROZEN)
    for rid, arrival in [(0, 0.2), (1, 0.0), (2, 0.1)]:
        eng.submit(Request(rid=rid, prompt=_prompt(rid, 4, cfg.vocab_size),
                           max_new_tokens=2, arrival=arrival))
    admitted = []
    orig = eng._admit

    def spy(req, slot):
        admitted.append(req.rid)
        orig(req, slot)

    eng._admit = spy
    while eng.step():
        pass
    assert admitted == [1, 2, 0]
    # all three finished with max_new_tokens tokens each
    assert sorted(eng.results) == [0, 1, 2]
    assert all(len(r.tokens) == 2 for r in eng.results.values())


def test_slot_reuse_after_eviction():
    """4 requests through 2 slots: each slot serves two requests, the
    second reusing the row the first freed — and the queue drains."""
    cfg, params = _cfg_params()
    eng = ContinuousBatchingEngine(
        cfg, params,
        EngineConfig(n_slots=2, max_ctx=16, max_prefills_per_tick=2,
                     backend="reference"),
        time_fn=_FROZEN)
    slots_used = {}
    orig = eng._admit

    def spy(req, slot):
        slots_used[req.rid] = slot
        orig(req, slot)

    eng._admit = spy
    reqs = [Request(rid=i, prompt=_prompt(i, 4, cfg.vocab_size),
                    max_new_tokens=3) for i in range(4)]
    results, metrics = eng.run(reqs)
    assert sorted(results) == [0, 1, 2, 3]
    # both slots were reused (2 requests per slot)
    assert sorted(slots_used.values()) == [0, 0, 1, 1]
    assert metrics["queue_depth_max"] >= 2
    assert metrics["n_prefills"] == 4


def test_late_arrival_waits_for_clock():
    """A request arriving in the future is not admitted while an earlier
    one decodes at now=0 (frozen clock), and the idle engine
    fast-forwards to its arrival instead of spinning."""
    cfg, params = _cfg_params()
    eng = ContinuousBatchingEngine(
        cfg, params,
        EngineConfig(n_slots=2, max_ctx=16, backend="reference"),
        time_fn=_FROZEN)
    eng.submit(Request(rid=0, prompt=_prompt(0, 4, cfg.vocab_size),
                       max_new_tokens=2, arrival=0.0))
    eng.submit(Request(rid=1, prompt=_prompt(1, 4, cfg.vocab_size),
                       max_new_tokens=2, arrival=5.0))
    while eng.step():
        if eng.n_active and 0 in {a.req.rid for a in eng.slots if a}:
            assert all(a.req.rid != 1 for a in eng.slots if a)
    assert eng.now >= 5.0                    # clock jumped to the arrival
    assert sorted(eng.results) == [0, 1]


# ------------------------------------------------------------ e2e parity

def test_engine_matches_greedy_generate_exactly():
    """Heterogeneous prompt lengths + staggered arrivals + slot reuse
    must emit bitwise-identical tokens to per-request greedy_generate."""
    cfg, params = _cfg_params()
    lens = [5, 8, 11, 4]
    reqs = [Request(rid=i, prompt=_prompt(i, L, cfg.vocab_size),
                    max_new_tokens=4, arrival=0.0 if i < 2 else 0.2)
            for i, L in enumerate(lens)]
    eng = ContinuousBatchingEngine(cfg, params,
                                   EngineConfig(n_slots=2, max_ctx=32))
    results, metrics = eng.run(reqs)
    assert metrics["requests"] == len(reqs)
    for r in reqs:
        ref = greedy_generate(params, cfg, jnp.asarray(r.prompt)[None],
                              n_steps=r.max_new_tokens, ctx=32)
        assert results[r.rid].tokens == list(np.asarray(ref[0])), \
            f"request {r.rid} diverged from greedy_generate"
    # accounting sanity
    for r in results.values():
        assert r.first_token_at >= r.arrival
        assert r.finished_at >= r.first_token_at


# ------------------------------------------- parity sweep: every config

def _fe_for(cfg, i):
    if not cfg.frontend:
        return None
    k = jax.random.fold_in(jax.random.PRNGKey(11), i)
    return np.asarray(jax.random.normal(k, (cfg.frontend_len, cfg.d_model))
                      * 0.02)


@pytest.mark.slow
@pytest.mark.parametrize("arch", configs.ASSIGNED + configs.PAPER_OWN)
def test_engine_parity_every_config(arch):
    """Every registered arch — full-context / MLA / rolling-window
    attention, RG-LRU, mLSTM+sLSTM, both MoEs, modality-frontend and
    encoder-decoder — serves through the continuous engine with
    bitwise-identical tokens to per-request greedy_generate.

    This is the engine's universality contract: MoE routing is
    per-token (length-invariant), stateful mixers prefill masked, and
    the ring cache keeps real positions only, so neither prompt-bucket
    padding nor co-batched slots can perturb a request's tokens."""
    cfg = configs.get(arch, smoke=True)
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    prefix = cfg.decode_prefix_len
    gen = 3
    max_ctx = max(prefix + 16 + gen, cfg.window)
    eng = ContinuousBatchingEngine(
        cfg, params, EngineConfig(n_slots=2, max_ctx=max_ctx,
                                  backend="reference"))
    reqs = [Request(rid=i, prompt=_prompt(i, L, cfg.vocab_size),
                    max_new_tokens=gen, arrival=0.0 if i < 2 else 0.1,
                    frontend=_fe_for(cfg, i))
            for i, L in enumerate((5, 9, 4))]
    results, metrics = eng.run(reqs)
    assert metrics["requests"] == len(reqs)
    # three prompts over two slots: heterogeneous buckets + slot reuse
    assert len(metrics["prefills_per_bucket"]) >= 2
    for r in reqs:
        fe = None if r.frontend is None else jnp.asarray(r.frontend)[None]
        ref = greedy_generate(params, cfg, jnp.asarray(r.prompt)[None],
                              n_steps=gen, ctx=max_ctx, frontend=fe)
        assert results[r.rid].tokens == list(np.asarray(ref[0])), \
            f"{arch}: request {r.rid} diverged from greedy_generate"


def test_reset_clears_all_accounting():
    """A warm rerun of the same trace after reset() must reproduce the
    first run's tokens and request-level accounting exactly (frozen
    clock): any surviving queue/metric/clock state would show up as a
    difference."""
    cfg, params = _cfg_params()
    eng = ContinuousBatchingEngine(
        cfg, params,
        EngineConfig(n_slots=2, max_ctx=16, backend="reference"),
        time_fn=_FROZEN)
    reqs = [Request(rid=i, prompt=_prompt(i, 4, cfg.vocab_size),
                    max_new_tokens=3, arrival=0.1 * i) for i in range(4)]
    res1, m1 = eng.run(list(reqs))
    toks1 = {rid: list(r.tokens) for rid, r in res1.items()}
    eng.reset()
    assert eng.now == 0.0 and not eng.pending and not eng.results
    assert eng.metrics()["requests"] == 0
    assert eng.metrics()["n_prefills"] == 0
    assert eng.metrics()["prefills_per_bucket"] == {}
    assert eng.metrics()["admission_wait_mean_s"] == 0.0
    res2, m2 = eng.run(list(reqs))
    assert {rid: list(r.tokens) for rid, r in res2.items()} == toks1
    assert m2 == m1


# ------------------------------------------- paged KV + radix sharing

def test_shared_prefix_parity_and_hit_rate():
    """Two requests sharing a 16-token system prompt: the second admit
    hits the radix tree (the prefix is prefilled once and continued
    over gathered pool pages), and both requests' tokens stay bitwise
    identical to independent greedy_generate."""
    cfg, params = _cfg_params()
    system = _prompt(100, 16, cfg.vocab_size)
    reqs = [Request(rid=i, prompt=system + _prompt(i, sl, cfg.vocab_size),
                    max_new_tokens=3)
            for i, sl in enumerate((3, 6))]
    eng = ContinuousBatchingEngine(cfg, params,
                                   EngineConfig(n_slots=2, max_ctx=32))
    results, metrics = eng.run(list(reqs))
    assert metrics["kv_layout"] == "paged"
    assert metrics["prefix_hit_rate"] > 0.0
    for r in reqs:
        ref = greedy_generate(params, cfg, jnp.asarray(r.prompt)[None],
                              n_steps=3, ctx=32, plan=eng.plan)
        assert results[r.rid].tokens == list(np.asarray(ref[0])), r.rid


def test_pages_reclaimed_after_finish():
    """Refcounts drop to zero at _finish: with sharing disabled the
    drained pool is completely free; with sharing enabled the only
    surviving references are the radix tree's own (+1) on the two
    registered full prompt pages, each at refcount exactly 1."""
    cfg, params = _cfg_params()
    system = _prompt(200, 16, cfg.vocab_size)
    reqs = [Request(rid=i, prompt=system + _prompt(i, sl, cfg.vocab_size),
                    max_new_tokens=2)
            for i, sl in enumerate((3, 6))]

    eng = ContinuousBatchingEngine(
        cfg, params, EngineConfig(n_slots=2, max_ctx=32,
                                  prefix_sharing=False))
    eng.run(list(reqs))
    assert eng.pool.n_free == eng.n_pages - 1
    assert not eng.pool.refs.any()
    assert (eng._page_table == 0).all()

    eng = ContinuousBatchingEngine(cfg, params,
                                   EngineConfig(n_slots=2, max_ctx=32))
    eng.run(list(reqs))
    held = np.flatnonzero(eng.pool.refs)
    # both prompts share the same two full 8-token prefix pages, so the
    # tree registered exactly those; everything else was reclaimed
    assert len(held) == 2
    assert (eng.pool.refs[held] == 1).all()
    assert eng.pool.n_free == eng.n_pages - 1 - len(held)


def test_eviction_never_frees_referenced_page():
    """Under page pressure the admission path evicts LRU radix leaves —
    but only pages the tree ALONE references.  An active request's
    pages keep refcount >= 1 through every tick, and its tokens still
    match greedy_generate after eviction churn."""
    cfg, params = _cfg_params()
    eng = ContinuousBatchingEngine(
        cfg, params, EngineConfig(n_slots=2, max_ctx=32, page_size=8,
                                  n_pages=7))
    reqs = [Request(rid=i, prompt=_prompt(300 + i, 16, cfg.vocab_size),
                    max_new_tokens=6) for i in range(3)]

    orig_step = eng.step

    def step_spy():
        alive = orig_step()
        for act in eng.slots:
            if act is None:
                continue
            assert all(eng.pool.refs[p] >= 1 for p in act.pages), \
                "eviction freed a page an active request references"
        return alive

    eng.step = step_spy
    results, metrics = eng.run(list(reqs))
    assert metrics["evictions"] > 0, "trace never hit page pressure"
    assert len(results) == len(reqs)
    for r in reqs:
        ref = greedy_generate(params, cfg, jnp.asarray(r.prompt)[None],
                              n_steps=6, ctx=32, plan=eng.plan)
        assert results[r.rid].tokens == list(np.asarray(ref[0])), r.rid


def test_int8_paged_engine_parity():
    """int8 KV rides the paged layout (quantized pools + scale pools,
    in-kernel dequant) bitwise-identically to greedy_generate's dense
    int8 ring; prefix SHARING stays off for int8 (a re-gathered prefix
    would attend over dequantized values where the original prefill
    attended raw)."""
    cfg, _ = _cfg_params()
    cfg = cfg.with_(kv_cache="int8")
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    eng = ContinuousBatchingEngine(cfg, params,
                                   EngineConfig(n_slots=2, max_ctx=32))
    assert eng.paged and not eng.sharable
    reqs = [Request(rid=i, prompt=_prompt(i, L, cfg.vocab_size),
                    max_new_tokens=3) for i, L in enumerate((5, 9))]
    results, _ = eng.run(list(reqs))
    for r in reqs:
        ref = greedy_generate(params, cfg, jnp.asarray(r.prompt)[None],
                              n_steps=3, ctx=32, plan=eng.plan)
        assert results[r.rid].tokens == list(np.asarray(ref[0])), r.rid


# ------------------------------------------- spans, scopes and uploads

def _host_spans(trace_dir):
    """[(name, start, end, stats)] of the profiler trace's host events on
    the thread that ran the ``caller`` span."""
    import glob
    import os
    path, = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    data = jax.profiler.ProfileData.from_file(path)
    for plane in data.planes:
        for line in plane.lines:
            evs = [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns,
                    dict(ev.stats)) for ev in line.events]
            if any(e[0] == "caller" for e in evs):
                return evs
    raise AssertionError("no caller span in the trace")


def _inside(child, parent):
    return parent[1] <= child[1] and child[2] <= parent[2]


def test_engine_spans_nest_under_the_callers_span(tmp_path):
    """Under jax.profiler.trace each tick is an engine.step span inside
    the caller's own span, holding engine.admit (with the request id)
    and engine.decode, each holding its named children."""
    cfg, params = _cfg_params()
    eng = ContinuousBatchingEngine(
        cfg, params, EngineConfig(n_slots=2, max_ctx=16,
                                  backend="reference"), time_fn=_FROZEN)
    reqs = [Request(rid=10 + i, prompt=_prompt(i, 4, cfg.vocab_size),
                    max_new_tokens=3) for i in range(2)]
    eng.run(list(reqs))                          # compile outside the trace
    eng.reset()
    with jax.profiler.trace(str(tmp_path)):
        with jax.profiler.TraceAnnotation("caller"):
            eng.run(list(reqs))
    evs = _host_spans(str(tmp_path))
    named = collections.defaultdict(list)
    for e in evs:
        named[e[0].split("#")[0]].append(e)
    caller, = named["caller"]
    steps = named["engine.step"]
    assert len(steps) == eng.n_decode_ticks + 1   # the last finds no work
    assert all(_inside(s, caller) for s in steps)

    def parent(e, among):
        return [p for p in among if _inside(e, p)]

    admits = named["engine.admit"]
    assert sorted(int(a[3]["rid"]) for a in admits) == [10, 11]
    for kind, children in (("engine.admit", ("engine.page_plan",
                                             "engine.prefill",
                                             "engine.admit_wait")),
                           ("engine.decode", ("engine.decode_dispatch",
                                              "engine.decode_wait",
                                              "engine.emit"))):
        outer = named[kind]
        assert outer and all(len(parent(o, steps)) == 1 for o in outer)
        for child in children:
            assert len(named[child]) == len(outer), child
            assert all(len(parent(c, outer)) == 1 for c in named[child])
    assert len(named["engine.decode"]) == eng.n_decode_ticks
    # one upload per admission and one per finish
    assert len(named["engine.page_table_upload"]) == 4


def test_h2d_uploads_count_every_host_array():
    """Two arrays a decode tick, five an admission without a prefix hit
    (page table, prompt, logit index, slot, prefix length) and one page
    table a finish; reset() zeroes the count."""
    cfg, params = _cfg_params()
    eng = ContinuousBatchingEngine(
        cfg, params, EngineConfig(n_slots=2, max_ctx=16,
                                  backend="reference"), time_fn=_FROZEN)
    assert eng.h2d_uploads == 0
    reqs = [Request(rid=i, prompt=_prompt(i, 4 + i, cfg.vocab_size),
                    max_new_tokens=2 + i, arrival=0.0) for i in range(3)]
    _, m = eng.run(list(reqs))
    assert m["prefix_hit_rate"] == 0.0
    assert m["h2d_uploads"] == eng.h2d_uploads == (
        2 * m["n_decode_ticks"] + 5 * m["n_prefills"] + 1 * len(reqs))
    eng.reset()
    assert eng.h2d_uploads == 0 and eng.metrics()["h2d_uploads"] == 0


def _scopes_in(lowered) -> set:
    """The named scopes in the location names of a lowered function (a
    scan body's own locations start below the scan)."""
    import re
    names = re.findall(r'loc\("([^"]*)"', lowered.as_text(debug_info=True))
    # a transformed scope reads e.g. transpose(jvp(attn))
    return {w for n in names for w in re.findall(r"\w+", n)} & set(_SCOPES)


_SCOPES = ("embed", "layers", "head", "attn", "mlp", "kv_write",
           "paged_gqa", "salr_base", "salr_base_decode", "salr_adapter")


def test_model_steps_carry_their_named_scopes():
    """The decode and prefill steps and the train step are traced under
    the named scopes that the trace reduction charges device time to."""
    from repro.optim.adamw import AdamW
    from repro.train.state import train_state_from_params
    from repro.train.step import make_train_step
    cfg, params = _cfg_params()
    eng = ContinuousBatchingEngine(cfg, params,
                                   EngineConfig(n_slots=2, max_ctx=16))
    tokens = jnp.zeros((2, 1), jnp.int32)
    pos = jnp.zeros((2,), jnp.int32)
    assert _scopes_in(eng._decode.lower(eng.params, eng.cache, tokens,
                                        pos)) == {
        "embed", "layers", "head", "attn", "mlp", "kv_write", "paged_gqa"}
    prompt = jnp.zeros((1, 8), jnp.int32)
    assert {"embed", "layers", "head", "attn", "mlp"} <= _scopes_in(
        eng._prefill.lower(eng.params, prompt, jnp.int32(3), None, None, 0))
    opt = AdamW(lr=1e-3)
    state = train_state_from_params(params, opt)
    step = jax.jit(make_train_step(cfg, opt))
    batch = {"tokens": jnp.zeros((2, 8), jnp.int32),
             "labels": jnp.zeros((2, 8), jnp.int32)}
    assert {"embed", "layers", "head", "attn", "mlp", "salr_base",
            "salr_base_decode", "salr_adapter"} <= _scopes_in(
        step.lower(state, batch))

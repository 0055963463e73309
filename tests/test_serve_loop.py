"""Live serving loop: traffic generator, offload scheduler, paged decode.

The load-bearing test is paged-vs-dense parity: the continuous-batching
loop (rows joining/leaving mid-flight, per-row cache positions, page-slab
gather/scatter) must produce exactly the greedy tokens the dense
``ServeEngine.generate`` produces per request — same weights, same prompts.
"""
import asyncio
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.models.registry import get_config, get_module
from repro.serve import (
    OffloadScheduler,
    ServeEngine,
    ServeLoop,
    ServeLoopConfig,
    TrafficConfig,
)
from repro.serve import traffic


@pytest.fixture(scope="module")
def arch():
    return get_config("granite_8b").reduced()


@pytest.fixture(scope="module")
def params(arch):
    return get_module(arch).init(jax.random.PRNGKey(0), arch)


# ------------------------------------------------------------------ traffic

def test_traffic_deterministic_and_bounded():
    cfg = TrafficConfig(n_requests=64, seed=5, arrival="poisson")
    a, b = traffic.generate(cfg), traffic.generate(cfg)
    assert [r.arrival_s for r in a] == [r.arrival_s for r in b]
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))
    for r in a:
        assert cfg.prompt_min <= r.prompt_len <= cfg.prompt_max
        assert cfg.decode_min <= r.decode_len <= cfg.decode_max
        assert r.prompt.dtype == np.int32
        assert r.prompt.min() >= 2 and r.prompt.max() < cfg.vocab_size
    arr = np.array([r.arrival_s for r in a])
    assert (np.diff(arr) >= 0).all() and arr[0] > 0
    # a different seed is a different stream
    c = traffic.generate(TrafficConfig(n_requests=64, seed=6))
    assert [r.arrival_s for r in c] != [r.arrival_s for r in a]


def test_traffic_heavy_tail_and_burstiness():
    flat = traffic.generate(TrafficConfig(
        n_requests=400, seed=0, prompt_tail=50.0))
    heavy = traffic.generate(TrafficConfig(
        n_requests=400, seed=0, prompt_tail=1.1))
    assert (np.mean([r.prompt_len for r in heavy])
            > np.mean([r.prompt_len for r in flat]))
    # bursty arrivals at the same mean rate have burstier inter-arrivals
    # (squared coefficient of variation well above the Poisson ~1)
    def cv2(reqs):
        d = np.diff([r.arrival_s for r in reqs])
        return float(np.var(d) / np.mean(d) ** 2)
    po = traffic.generate(TrafficConfig(n_requests=500, seed=2))
    bu = traffic.generate(TrafficConfig(n_requests=500, seed=2,
                                        arrival="bursty"))
    assert cv2(bu) > cv2(po) * 1.5


def test_traffic_validation():
    with pytest.raises(ValueError, match="arrival"):
        traffic.generate(TrafficConfig(arrival="uniform"))
    with pytest.raises(ValueError, match="rate_rps"):
        traffic.generate(TrafficConfig(rate_rps=0.0))
    with pytest.raises(ValueError, match="lo"):
        traffic.generate(TrafficConfig(prompt_min=10, prompt_max=4))
    assert TrafficConfig(seed=9).asdict()["seed"] == 9


# ---------------------------------------------------------------- scheduler

def test_scheduler_prices_decode_batch(arch):
    sch = OffloadScheduler(n_arrays=4)
    p1 = sch.price_decode_batch(arch, 1)
    assert p1.modeled_s > 0 and p1.makespan_cycles > 0
    assert p1.n_arrays == 4 and len(p1.per_array_cycles) == 4
    # makespan semantics: slowest array, bounded by sum/n and sum
    total = sum(p1.per_array_cycles)
    assert max(p1.per_array_cycles) == p1.makespan_cycles
    assert total / 4 <= p1.makespan_cycles <= total
    # bigger batch costs more; repeated query hits the cache
    p8 = sch.price_decode_batch(arch, 8)
    assert p8.makespan_cycles >= p1.makespan_cycles
    assert sch.price_decode_batch(arch, 1) is p1


def test_scheduler_sparse_price_matches_mesh_model():
    from repro.core.perf_model import (MeshSparseMTTKRPWorkload,
                                       mesh_sparse_price)
    from repro.backends.base import resolve_config

    fibers = np.array([100, 40, 7, 3, 1] * 8)
    sch = OffloadScheduler(n_arrays=4)
    p = sch.price_sparse(fibers, rank=16)
    ref = mesh_sparse_price(resolve_config(None), MeshSparseMTTKRPWorkload(
        fiber_lengths=fibers, rank=16, n_arrays=4))
    assert p.makespan_cycles == ref.makespan_cycles
    assert p.reduce_cycles == ref.reduce_cycles
    assert p.modeled_s == pytest.approx(
        ref.duration_s(resolve_config(None)))


def test_scheduler_host_fallback(arch):
    sch = OffloadScheduler(n_arrays=2)
    # unmeasured host -> optimistic offload
    assert sch.decide_decode(arch, 2).target == "psram"
    # a host faster than the modeled mesh wins
    sch.observe_host(2, 1e-12)
    d = sch.decide_decode(arch, 2)
    assert d.target == "host" and not d.offloaded
    assert d.host_s == pytest.approx(1e-12)
    # a glacial host flips it back (EMA converges toward new observations)
    for _ in range(40):
        sch.observe_host(2, 10.0)
    assert sch.decide_decode(arch, 2).target == "psram"
    with pytest.raises(ValueError, match="at least one array"):
        OffloadScheduler(n_arrays=0)


# -------------------------------------------------- per-row decode positions

def test_vector_cache_pos_matches_scalar(arch, params):
    """A (B,) cache_pos with equal entries must equal the scalar path."""
    mod = get_module(arch)
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 2,
                              arch.vocab_size)
    logits, cache = mod.prefill(params, toks, arch, cache_len=16)
    nxt = jnp.argmax(logits, -1).astype(jnp.int32)
    l_s, c_s = mod.decode_step(params, cache, nxt, jnp.int32(8), arch)
    l_v, c_v = mod.decode_step(params, cache, nxt,
                               jnp.full((2,), 8, jnp.int32), arch)
    np.testing.assert_array_equal(np.asarray(l_s), np.asarray(l_v))
    for a, b in zip(jax.tree.leaves(c_s), jax.tree.leaves(c_v)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------- the loop

def _loop(arch, params, **kw):
    lc = dict(max_batch=4, num_pages=24, page_size=8, speedup=200.0)
    lc.update(kw)
    return ServeLoop(arch, params, ServeLoopConfig(**lc))


def test_loop_drains_without_leaks_and_matches_dense(arch, params):
    tc = TrafficConfig(n_requests=40, seed=1, rate_rps=60.0,
                       prompt_min=2, prompt_max=24, decode_min=2,
                       decode_max=12, vocab_size=arch.vocab_size)
    loop = _loop(arch, params)
    rep = loop.run_sync(tc)
    s = rep.summary()
    assert s["completed"] + s["rejected"] == 40
    assert s["completed"] >= 38
    assert s["leaked_pages"] == 0             # every page freed at drain
    assert loop.kv.allocated_pages == 0
    assert s["p99_latency_s"] >= s["p50_latency_s"] > 0
    assert s["throughput_tok_s"] > 0
    # modeled makespan is recorded alongside every measured step
    assert rep.offload and all(
        o["measured_s"] > 0 and o["modeled_s"] > 0 and
        o["makespan_cycles"] > 0 for o in rep.offload)

    # parity: every completed-without-preemption request reproduces the
    # dense engine's greedy tokens despite ragged continuous batching
    eng = ServeEngine(arch, params, max_len=64)
    reqs = {r.rid: r for r in traffic.generate(tc)}
    checked = 0
    for rec in rep.completed[:12]:
        if rec.preemptions:
            continue
        r = reqs[rec.rid]
        toks = eng.generate(jnp.asarray(r.prompt[None]), r.prompt_len,
                            max_new_tokens=rec.n_generated)
        assert [int(t) for t in np.asarray(toks[0])] == rec.tokens
        checked += 1
    assert checked >= 8


def test_warmup_compiles_buckets_without_corruption(arch, params):
    # warmup touches only the sacrificial pad slot: a post-warmup run
    # produces the same tokens and still drains leak-free
    tc = TrafficConfig(n_requests=6, seed=3, rate_rps=80.0,
                       prompt_min=2, prompt_max=20, decode_min=2,
                       decode_max=10, vocab_size=arch.vocab_size)
    cold = _loop(arch, params).run_sync(tc)
    warm_loop = _loop(arch, params)
    # prompts up to 20 -> pad buckets 8/16/32; positions up to 29 -> view
    # buckets 8/16/32: 3 + 3 compiled calls
    assert warm_loop.warmup(max_prompt=20, max_decode=10) == 6
    assert warm_loop.kv.allocated_pages == 0
    warm = warm_loop.run_sync(tc)
    assert warm.summary()["leaked_pages"] == 0
    by_rid = {r.rid: r.tokens for r in cold.completed if not r.preemptions}
    matched = 0
    for rec in warm.completed:
        if rec.preemptions or rec.rid not in by_rid:
            continue
        assert rec.tokens == by_rid[rec.rid]
        matched += 1
    assert matched >= 4


def test_loop_preempts_youngest_under_page_pressure(arch, params):
    # 8 pages x 4 = 32 slots; two (4 prompt + 20 decode) requests need
    # 6 pages each -> they must collide mid-decode and one must recompute
    obs.enable()
    try:
        loop = _loop(arch, params, max_batch=4, num_pages=8, page_size=4,
                     speedup=1000.0)
        tc = TrafficConfig(n_requests=5, seed=3, rate_rps=500.0,
                           prompt_min=4, prompt_max=4, decode_min=20,
                           decode_max=20, vocab_size=arch.vocab_size)
        rep = loop.run_sync(tc)
        assert rep.preemptions >= 1
        assert rep.leaked_pages == 0
        assert all(r.n_generated == 20 for r in rep.completed)
        assert len(rep.completed) == 5
        counters = obs.get_tracer().counters()
        assert counters["serve/preempted"] == rep.preemptions
        assert counters["serve/admitted"] >= 5 + rep.preemptions
        # tokens, prefills and steps are the report's, not counters
        assert not {"serve/prefills", "serve/decode_steps",
                    "serve/tokens"} & set(counters)
        names = {e["name"] for e in obs.get_tracer().events()}
        assert {"serve/admit", "serve/prefill", "serve/decode",
                "serve/offload", "serve/evict", "serve/step",
                "serve/decode/build", "serve/sample",
                "serve/enqueue"} <= names
    finally:
        obs.disable()
        obs.get_tracer().clear()


def _inside(inner, outer, eps_us=1e-3):
    """Whether ``inner`` lies within ``outer`` (µs, rounding allowed)."""
    return (outer["ts"] - eps_us <= inner["ts"] and inner["ts"] + inner["dur"]
            <= outer["ts"] + outer["dur"] + eps_us)


@pytest.fixture(scope="module")
def spaced_run(arch, params):
    """A warmed loop over three short requests due 0.4 s apart, traced:
    each finishes long before the next is due, so the loop goes idle
    before each arrival (the first included: the engine's first iteration
    runs before the producer)."""
    reqs = traffic.generate(TrafficConfig(
        n_requests=3, seed=4, rate_rps=100.0, prompt_min=4, prompt_max=8,
        decode_min=3, decode_max=3, vocab_size=arch.vocab_size))
    reqs = [dataclasses.replace(r, arrival_s=0.4 * i)
            for i, r in enumerate(reqs)]
    loop = _loop(arch, params, speedup=1.0)
    loop.warmup(max_prompt=8, max_decode=3)
    obs.get_tracer().clear()
    obs.enable()
    try:
        rep = loop.run_sync(reqs)
        events = obs.get_tracer().events()
    finally:
        obs.disable()
        obs.get_tracer().clear()
    return reqs, rep, events


def _named(events, name):
    return [e for e in events if e["name"] == name]


def test_loop_records_one_idle_span_per_idle_stretch(spaced_run):
    reqs, rep, events = spaced_run
    recs = rep.records
    assert len(rep.completed) == 3
    # the premise: every request finished before the next was released
    for prev, nxt in zip(recs, recs[1:]):
        assert prev.finished_s < nxt.arrival_s
    idle = _named(events, "serve/idle")
    enqueue = _named(events, "serve/enqueue")
    assert len(idle) == len(reqs)             # one per stretch, not per poll
    for span in idle:                          # each ended by one arrival
        assert sum(_inside(e, span) for e in enqueue) == 1
    assert not any(_inside(e, span) for span in idle
                   for e in _named(events, "serve/step"))


def test_loop_enqueue_span_carries_rid_and_lateness(spaced_run):
    reqs, _, events = spaced_run
    enqueue = _named(events, "serve/enqueue")
    assert sorted(e["args"]["rid"] for e in enqueue) == sorted(
        r.rid for r in reqs)
    assert all(e["args"]["late_ms"] >= 0.0 for e in enqueue)


def test_loop_step_span_holds_one_decode(spaced_run):
    _, rep, events = spaced_run
    steps = _named(events, "serve/step")
    assert len(steps) == rep.n_steps > 0
    for child in ("serve/decode", "serve/offload", "serve/decode/build",
                  "serve/sample"):
        spans = _named(events, child)
        assert len(spans) == len(steps)
        for step in steps:
            assert sum(_inside(e, step) for e in spans) == 1


def test_loop_preemption_cap_fails_cleanly(arch, params):
    # same page-pressure collision as above, but with zero retries allowed:
    # the first eviction must fail the victim with a recorded reason rather
    # than requeue it — bounded preemption can never livelock the loop
    loop = _loop(arch, params, max_batch=4, num_pages=8, page_size=4,
                 speedup=1000.0, max_preemptions=0)
    tc = TrafficConfig(n_requests=5, seed=3, rate_rps=500.0,
                       prompt_min=4, prompt_max=4, decode_min=20,
                       decode_max=20, vocab_size=arch.vocab_size)
    rep = loop.run_sync(tc)
    assert rep.preemptions >= 1
    assert rep.failed and all(r.failure == "preempt-limit"
                              for r in rep.failed)
    assert len(rep.completed) + len(rep.failed) == 5
    assert all(r.n_generated == 20 for r in rep.completed)
    assert rep.leaked_pages == 0              # failure still frees pages
    s = rep.summary()
    assert s["failed"] == len(rep.failed)
    assert s["failure_reasons"] == {"preempt-limit": len(rep.failed)}


def test_loop_deadline_sheds_overdue_requests(arch, params):
    # an absurdly tight deadline: every request is overdue by the time the
    # shed check sees it, so the loop fails all of them with "deadline"
    # and never decodes — admission shedding, not silent stalling
    loop = _loop(arch, params, speedup=1000.0, deadline_s=1e-9)
    tc = TrafficConfig(n_requests=4, seed=2, rate_rps=200.0,
                       prompt_min=2, prompt_max=8, decode_min=2,
                       decode_max=4, vocab_size=arch.vocab_size)
    rep = loop.run_sync(tc)
    assert not rep.completed
    assert len(rep.failed) == 4
    assert all(r.failure == "deadline" for r in rep.failed)
    assert rep.leaked_pages == 0
    assert rep.summary()["failure_reasons"] == {"deadline": 4}
    # a roomy deadline changes nothing: the same stream completes
    roomy = _loop(arch, params, speedup=1000.0, deadline_s=300.0)
    assert len(roomy.run_sync(tc).completed) == 4


def test_loop_rejects_never_fitting_requests(arch, params):
    loop = _loop(arch, params, max_batch=2, num_pages=8, page_size=4,
                 speedup=1000.0)
    tc = TrafficConfig(n_requests=3, seed=0, rate_rps=100.0,
                       prompt_min=40, prompt_max=40, decode_min=4,
                       decode_max=4, vocab_size=arch.vocab_size)
    rep = loop.run_sync(tc)
    assert len(rep.rejected) == 3 and not rep.completed
    assert rep.leaked_pages == 0 and rep.n_steps == 0


def test_loop_accepts_request_list_and_async(arch, params):
    reqs = traffic.generate(TrafficConfig(
        n_requests=4, seed=2, rate_rps=200.0, prompt_min=2, prompt_max=8,
        decode_min=2, decode_max=4, vocab_size=arch.vocab_size))
    loop = _loop(arch, params, speedup=1000.0)
    rep = asyncio.run(loop.run(reqs))
    assert len(rep.completed) == 4
    for rec in rep.completed:
        assert rec.ttft_s is not None and rec.latency_s >= rec.ttft_s


# ------------------------------------------------------------------ guards

def test_paged_builders_guard_unsupported_families(arch):
    from repro.serve.engine import make_prefill, make_serve_step

    enc = get_config("seamless_m4t_large_v2")
    with pytest.raises(ValueError, match="delta-form"):
        make_serve_step(enc, deltas=True)
    with pytest.raises(ValueError, match="paged prefill"):
        make_prefill(enc, paged=True)
    with pytest.raises(ValueError, match="cache_len"):
        make_prefill(arch)


def test_loop_guards_non_kv_cache_state():
    ssm = get_config("mamba2_370m").reduced()
    with pytest.raises(ValueError, match="all-attention"):
        ServeLoop(ssm, loop_cfg=ServeLoopConfig(num_pages=4, page_size=4))

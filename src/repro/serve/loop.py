"""Live serving loop: admission, continuous batching, paged KV, offload.

This is the subsystem the rest of ``repro.serve`` was building toward — an
actual request loop instead of the fixed-batch ``ServeEngine.generate``.
Requests arrive on a (synthetic, seeded) timeline (`repro.serve.traffic`),
wait in a FIFO admission queue gated by ``PagedKVManager.can_admit``, and
decode under *continuous batching*: rows join and leave the batch between
steps, every row at its own sequence length.

How the pieces fit:

* **physical KV = one page slab per cache leaf.** The model's dense cache
  leaf ``(G, B, S, Hkv, hd)`` becomes a slab ``(num_slots + 1, G, Hkv, hd)``
  with ``num_slots = num_pages * page_size`` token slots addressed by the
  page tables of :class:`~repro.serve.kv_cache.PagedKVManager`. Slot
  ``num_slots`` is sacrificial: padding rows gather from and scatter to it,
  so ragged batches need no masking on the memory side. The slab is donated
  through every jitted call — there is exactly one copy alive.
* **decode = gather / step / scatter.** Each step gathers every active
  row's slots into a dense ``(G, B, S_v, Hkv, hd)`` view
  (:func:`~repro.serve.kv_cache.gather_cache`), runs the model's delta-form
  step (``make_serve_step(cfg, deltas=True)`` — per-row vector
  ``cache_pos``), and scatters the one-token deltas back to each row's
  newest slot. Stale slots beyond a row's length are masked *inside* the
  attention (``k_pos < cache_pos``), which is what makes
  extend-before-step safe.
* **bounded retracing.** Prompts right-pad and the gather view rounds up
  to power-of-two buckets, so jit retraces O(log capacity) times total,
  not per request. Prefill takes its logits at the traced index
  ``prompt_len - 1`` — one compile per bucket, not per length.
* **admission / preemption.** Admission is FIFO with head-of-line
  blocking; a request whose prompt (or prompt + decode budget) can never
  fit is rejected up front. When a mid-decode page allocation fails, the
  *youngest* live row is preempted (pages freed, its request requeued at
  the queue front, generated tokens discarded — recompute-style), which
  guarantees forward progress for the oldest row.
* **offload.** Before each decode step the
  :class:`~repro.serve.scheduler.OffloadScheduler` prices the batch's
  projection matmuls on the pSRAM mesh (counted cycles, LPT makespan over
  ``n_arrays``) and decides pSRAM-vs-host against the measured host EMA.
  Execution stays on host (there is no photonic silicon in this
  container); the decision trail — modeled makespan next to measured step
  wall time, per batch — is recorded in ``ServeReport.offload`` and
  lands in the ``serve_*`` bench rows.

Every phase is observable (`repro.obs`): spans ``serve/idle``,
``serve/enqueue``, ``serve/admit``, ``serve/prefill``, ``serve/evict``,
``serve/step`` (children ``serve/offload``, ``serve/decode/build``,
``serve/decode``, ``serve/sample``) and ``serve/fail``; counters
``serve/admitted``, ``serve/rejected``, ``serve/preempted``,
``serve/failed``. The list, with each span's args, is in
:mod:`repro.obs`.

The loop is a single-consumer ``asyncio`` engine: a producer task releases
requests at their (speedup-scaled) arrival times while the engine task
alternates admit/step, yielding between steps. ``run_sync`` wraps it for
scripts and tests.
"""
from __future__ import annotations

import asyncio
import dataclasses
from collections import deque
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.models.registry import get_module
from repro.serve import traffic as traffic_mod
from repro.serve.engine import make_prefill, make_serve_step
from repro.serve.kv_cache import PagedCacheConfig, PagedKVManager, gather_cache
from repro.serve.scheduler import OffloadScheduler


@dataclasses.dataclass(frozen=True)
class ServeLoopConfig:
    """Engine knobs (model-independent; the model comes from ArchConfig)."""

    max_batch: int = 8            # decode rows (static jit batch dimension)
    num_pages: int = 64
    page_size: int = 16
    temperature: float = 0.0      # 0 = greedy; >0 = seeded gumbel sampling
    sample_seed: int = 0
    speedup: float = 1.0          # arrival-time compression: wall = sim/speedup
    min_bucket: int = 8           # smallest pad/view bucket (powers of two up)
    idle_poll_s: float = 0.0005   # engine sleep when nothing is runnable
    max_preemptions: int = 8      # evictions per request before it fails
                                  # cleanly ("preempt-limit") — page pressure
                                  # can delay a request but never livelock it
    deadline_s: float | None = None  # per-request wall deadline since arrival
                                     # (post-speedup); None = no timeouts.
                                     # Overdue queued requests are shed at
                                     # admission, overdue active rows fail
                                     # and free their pages ("deadline")


@dataclasses.dataclass
class RequestRecord:
    """Per-request lifecycle timestamps (seconds since run start, wall)."""

    rid: int
    prompt_len: int
    decode_len: int
    arrival_s: float | None = None
    admitted_s: float | None = None
    first_token_s: float | None = None
    finished_s: float | None = None
    n_generated: int = 0
    preemptions: int = 0
    rejected: bool = False
    failed: bool = False
    failure: str | None = None    # "preempt-limit" | "deadline" when failed
    tokens: list[int] = dataclasses.field(default_factory=list)

    @property
    def finished(self) -> bool:
        return self.finished_s is not None

    @property
    def latency_s(self) -> float | None:
        if self.finished_s is None or self.arrival_s is None:
            return None
        return self.finished_s - self.arrival_s

    @property
    def ttft_s(self) -> float | None:
        if self.first_token_s is None or self.arrival_s is None:
            return None
        return self.first_token_s - self.arrival_s


@dataclasses.dataclass
class ServeReport:
    """What one run did: per-request records + engine-level aggregates."""

    records: list[RequestRecord]
    duration_s: float
    n_prefills: int
    n_steps: int
    preemptions: int
    leaked_pages: int             # pages still allocated after drain: must be 0
    peak_utilization: float
    mean_fragmentation: float
    offload: list[dict]           # per-step: target, modeled_s, measured_s, ...
    speedup: float

    @property
    def completed(self) -> list[RequestRecord]:
        return [r for r in self.records if r.finished]

    @property
    def rejected(self) -> list[RequestRecord]:
        return [r for r in self.records if r.rejected]

    @property
    def failed(self) -> list[RequestRecord]:
        return [r for r in self.records if r.failed]

    def _pct(self, values, q) -> float:
        return float(np.percentile(np.asarray(values), q)) if values else 0.0

    @property
    def p50_latency_s(self) -> float:
        return self._pct([r.latency_s for r in self.completed], 50)

    @property
    def p99_latency_s(self) -> float:
        return self._pct([r.latency_s for r in self.completed], 99)

    @property
    def p50_ttft_s(self) -> float:
        return self._pct([r.ttft_s for r in self.completed], 50)

    @property
    def p99_ttft_s(self) -> float:
        return self._pct([r.ttft_s for r in self.completed], 99)

    @property
    def throughput_rps(self) -> float:
        return len(self.completed) / max(self.duration_s, 1e-9)

    @property
    def throughput_tok_s(self) -> float:
        toks = sum(r.n_generated for r in self.completed)
        return toks / max(self.duration_s, 1e-9)

    @property
    def offload_fraction(self) -> float:
        if not self.offload:
            return 0.0
        hits = sum(1 for o in self.offload if o["target"] == "psram")
        return hits / len(self.offload)

    def summary(self) -> dict:
        """JSON-ready aggregate view — what the serve_* bench rows record."""
        modeled = [o["modeled_s"] for o in self.offload]
        measured = [o["measured_s"] for o in self.offload]
        failures: dict[str, int] = {}
        for r in self.failed:
            failures[r.failure or "?"] = failures.get(r.failure or "?", 0) + 1
        return {
            "completed": len(self.completed),
            "rejected": len(self.rejected),
            "failed": len(self.failed),
            "failure_reasons": failures,
            "preemptions": self.preemptions,
            "leaked_pages": self.leaked_pages,
            "duration_s": self.duration_s,
            "p50_latency_s": self.p50_latency_s,
            "p99_latency_s": self.p99_latency_s,
            "p50_ttft_s": self.p50_ttft_s,
            "p99_ttft_s": self.p99_ttft_s,
            "throughput_rps": self.throughput_rps,
            "throughput_tok_s": self.throughput_tok_s,
            "offload_fraction": self.offload_fraction,
            "mean_modeled_step_s": float(np.mean(modeled)) if modeled else 0.0,
            "mean_measured_step_s": (float(np.mean(measured))
                                     if measured else 0.0),
            "peak_utilization": self.peak_utilization,
            "mean_fragmentation": self.mean_fragmentation,
        }


@dataclasses.dataclass
class _Active:
    """One live decode row."""

    req: traffic_mod.Request
    row: int
    admit_seq: int                # monotonically increasing admission order
    next_token: int               # the token the next step feeds
    pos: int                      # tokens written to the KV (cache_pos)
    generated: list[int]


class ServeLoop:
    """The live engine. One instance owns one page slab + one KV manager;
    ``run`` / ``run_sync`` drive a request list (or a TrafficConfig)
    through it and return a :class:`ServeReport`."""

    def __init__(self, cfg, params=None, loop_cfg: ServeLoopConfig | None = None,
                 scheduler: OffloadScheduler | None = None):
        self.cfg = cfg
        self.loop_cfg = loop_cfg or ServeLoopConfig()
        self.mod = get_module(cfg)
        self.params = params if params is not None else \
            self.mod.init(jax.random.PRNGKey(0), cfg)
        self.scheduler = scheduler or OffloadScheduler()
        self.kv = PagedKVManager(PagedCacheConfig(
            num_pages=self.loop_cfg.num_pages,
            page_size=self.loop_cfg.page_size))
        self._rng = np.random.default_rng(self.loop_cfg.sample_seed)

        template = self.mod.init_cache(cfg, 1, 1)
        if any(leaf.ndim != 5 for leaf in jax.tree.leaves(template)):
            raise ValueError(
                f"family {cfg.family!r} carries non-KV cache state (conv/ssm "
                "recurrences); the paged serve loop supports all-attention "
                "layouts")
        self._pad_slot = self.kv.cfg.capacity_tokens
        n_slots = self._pad_slot + 1  # +1 sacrificial slot for padding rows

        def slab_of(leaf):
            g, _, _, hkv, hd = leaf.shape
            return jnp.zeros((n_slots, g, hkv, hd), dtype=leaf.dtype)

        self.slab = jax.tree.map(slab_of, template)
        self._prefill_fn = jax.jit(make_prefill(cfg, paged=True))
        step = make_serve_step(cfg, deltas=True)

        @partial(jax.jit, donate_argnums=(0,))
        def scatter_prefill(slab, caches, slots):
            # caches leaf (G, 1, S_pad, Hkv, hd) -> (S_pad, G, Hkv, hd);
            # pad positions in `slots` all point at the sacrificial slot
            def one(slab_leaf, cache_leaf):
                upd = jnp.transpose(cache_leaf[:, 0], (1, 0, 2, 3))
                return slab_leaf.at[slots].set(upd.astype(slab_leaf.dtype))

            return jax.tree.map(one, slab, caches)

        @partial(jax.jit, donate_argnums=(1,))
        def decode(params, slab, token, cache_pos, gather_idx, new_slots):
            # gather: slab leaf (n_slots, G, Hkv, hd)[(B, S_v)] ->
            # (B, S_v, G, Hkv, hd) -> the model's (G, B, S_v, Hkv, hd)
            view = jax.tree.map(
                lambda leaf: jnp.transpose(
                    gather_cache(leaf, gather_idx), (2, 0, 1, 3, 4)),
                slab)
            logits, deltas = step(params, view, token, cache_pos)

            def one(slab_leaf, delta):
                # delta (G, B, 1, Hkv, hd) -> (B, G, Hkv, hd): row i's new
                # token lands in its own slot (inactive rows -> sacrificial)
                upd = jnp.transpose(delta[:, :, 0], (1, 0, 2, 3))
                return slab_leaf.at[new_slots].set(upd.astype(slab_leaf.dtype))

            return logits, jax.tree.map(one, slab, deltas)

        self._scatter_fn = scatter_prefill
        self._decode_fn = decode

    # ---------------------------------------------------------------- helpers
    def _bucket(self, n: int) -> int:
        b = self.loop_cfg.min_bucket
        while b < n:
            b *= 2
        return b

    def _sample(self, logits: np.ndarray) -> np.ndarray:
        if self.loop_cfg.temperature <= 0.0:
            return np.argmax(logits, axis=-1).astype(np.int32)
        g = self._rng.gumbel(size=logits.shape)
        return np.argmax(
            logits / self.loop_cfg.temperature + g, axis=-1).astype(np.int32)

    def _never_fits(self, req) -> bool:
        """True when no amount of waiting could admit + finish this request."""
        kv = self.kv
        return (kv.pages_needed(req.prompt_len) + 1 > kv.cfg.num_pages
                or kv.pages_needed(req.prompt_len + req.decode_len)
                > kv.cfg.num_pages)

    def _prefill_one(self, req) -> int:
        """Prefill one admitted request into its pages; returns its first
        generated token."""
        s_pad = self._bucket(req.prompt_len)
        toks = np.zeros((1, s_pad), np.int32)
        toks[0, :req.prompt_len] = req.prompt
        logits, caches = self._prefill_fn(
            self.params, jnp.asarray(toks), jnp.int32(req.prompt_len - 1))
        slots = np.full(s_pad, self._pad_slot, np.int32)
        slots[:req.prompt_len] = self.kv.physical_slots(req.rid)
        self.slab = self._scatter_fn(self.slab, caches, jnp.asarray(slots))
        return int(self._sample(np.asarray(logits))[0])

    def warmup(self, max_prompt: int, max_decode: int) -> int:
        """Compile every jit shape bucket a stream with prompts up to
        ``max_prompt`` and decodes up to ``max_decode`` can hit, so the
        first measured requests aren't compile-dominated.

        Runs each prefill pad bucket and each decode view bucket once with
        dummy inputs routed entirely at the sacrificial pad slot (whose
        contents are never read unmasked), so the KV pool and the slab's
        live rows are untouched. Returns the number of compiled calls."""
        lc = self.loop_cfg
        n = 0
        b = lc.min_bucket
        while True:
            toks = jnp.zeros((1, b), np.int32)
            _, caches = self._prefill_fn(self.params, toks, jnp.int32(0))
            slots = jnp.full(b, self._pad_slot, np.int32)
            self.slab = self._scatter_fn(self.slab, caches, slots)
            n += 1
            if b >= max_prompt:
                break
            b *= 2
        s_v = lc.min_bucket
        while True:
            logits, self.slab = self._decode_fn(
                self.params, self.slab,
                jnp.zeros(lc.max_batch, np.int32),
                jnp.zeros(lc.max_batch, np.int32),
                jnp.full((lc.max_batch, s_v), self._pad_slot, np.int32),
                jnp.full(lc.max_batch, self._pad_slot, np.int32))
            jax.block_until_ready(logits)
            n += 1
            if s_v >= max_prompt + max_decode:
                break
            s_v *= 2
        return n

    # ------------------------------------------------------------------- run
    async def run(self, requests) -> ServeReport:
        if isinstance(requests, traffic_mod.TrafficConfig):
            requests = traffic_mod.generate(requests)
        lc = self.loop_cfg
        aloop = asyncio.get_running_loop()
        t0 = aloop.time()

        def now() -> float:
            return aloop.time() - t0

        queue: deque = deque()
        records = {
            r.rid: RequestRecord(rid=r.rid, prompt_len=r.prompt_len,
                                 decode_len=r.decode_len)
            for r in requests
        }
        done_producing = asyncio.Event()

        async def producer():
            for r in sorted(requests, key=lambda q: q.arrival_s):
                due = r.arrival_s / lc.speedup
                while (delay := due - now()) > 0:
                    await asyncio.sleep(delay)
                t = records[r.rid].arrival_s = now()
                with obs.span("serve/enqueue", rid=r.rid,
                              late_ms=1e3 * (t - due)):
                    queue.append(r)
            done_producing.set()

        prod = asyncio.ensure_future(producer())

        active: list[_Active | None] = [None] * lc.max_batch
        free_rows = list(reversed(range(lc.max_batch)))
        offload_log: list[dict] = []
        n_prefills = n_steps = preemptions = admit_seq = 0
        peak_util = frag_sum = 0.0
        frag_n = 0

        def finish(a: _Active):
            rec = records[a.req.rid]
            rec.finished_s = now()
            rec.n_generated = len(a.generated)
            rec.tokens = list(a.generated)
            self.kv.free_request(a.req.rid)
            active[a.row] = None
            free_rows.append(a.row)

        def fail(rid: int, reason: str):
            rec = records[rid]
            rec.failed = True
            rec.failure = reason
            obs.counter("serve/failed")
            with obs.span("serve/fail", rid=rid, reason=reason):
                pass

        def fail_active(a: _Active, reason: str):
            fail(a.req.rid, reason)
            records[a.req.rid].n_generated = len(a.generated)
            self.kv.free_request(a.req.rid)
            active[a.row] = None
            free_rows.append(a.row)

        def overdue(rid: int) -> bool:
            if lc.deadline_s is None:
                return False
            arr = records[rid].arrival_s
            return arr is not None and now() - arr > lc.deadline_s

        # one span per idle stretch: opened by an iteration that finds
        # nothing queued or active, closed when work arrives
        idle = None
        try:
            while not (done_producing.is_set() and not queue
                       and all(a is None for a in active)):
                progressed = False
                if idle is not None and (queue or len(free_rows)
                                         < lc.max_batch):
                    idle.__exit__(None, None, None)
                    idle = None

                # -- deadlines: shed overdue queued work, time out live rows
                if lc.deadline_s is not None:
                    while queue and overdue(queue[0].rid):
                        fail(queue.popleft().rid, "deadline")
                        progressed = True
                    for a in list(active):
                        if a is not None and overdue(a.req.rid):
                            fail_active(a, "deadline")
                            progressed = True

                # -- admit: FIFO, head-of-line blocking ---------------------
                with obs.span("serve/admit", queued=len(queue)):
                    while queue and free_rows:
                        req = queue[0]
                        if self._never_fits(req):
                            queue.popleft()
                            records[req.rid].rejected = True
                            obs.counter("serve/rejected")
                            progressed = True
                            continue
                        if not self.kv.can_admit(req.prompt_len):
                            break
                        queue.popleft()
                        self.kv.admit(req.rid, req.prompt_len)
                        rec = records[req.rid]
                        rec.admitted_s = now()
                        obs.counter("serve/admitted")
                        with obs.stopwatch("serve/prefill", rid=req.rid,
                                           prompt=req.prompt_len):
                            tok = self._prefill_one(req)
                        if rec.first_token_s is None:
                            rec.first_token_s = now()
                        n_prefills += 1
                        a = _Active(req=req, row=free_rows.pop(),
                                    admit_seq=admit_seq, next_token=tok,
                                    pos=req.prompt_len, generated=[tok])
                        admit_seq += 1
                        active[a.row] = a
                        progressed = True
                        if len(a.generated) >= req.decode_len:
                            finish(a)

                # -- decode: extend (evicting under pressure), step ---------
                step_rows = sorted((a for a in active if a is not None),
                                   key=lambda a: a.admit_seq)
                if step_rows:
                    i = 0
                    while i < len(step_rows):
                        a = step_rows[i]
                        if self.kv.extend(a.req.rid, 1):
                            i += 1
                            continue
                        victim = step_rows[-1]  # youngest live row
                        with obs.span("serve/evict", rid=victim.req.rid):
                            self.kv.free_request(victim.req.rid)
                            active[victim.row] = None
                            free_rows.append(victim.row)
                            rec_v = records[victim.req.rid]
                            rec_v.preemptions += 1
                            preemptions += 1
                            obs.counter("serve/preempted")
                            if rec_v.preemptions > lc.max_preemptions:
                                # bounded retries exhausted: fail cleanly
                                # instead of requeueing — page pressure can
                                # never livelock the loop
                                fail(victim.req.rid, "preempt-limit")
                            else:
                                queue.appendleft(victim.req)
                        step_rows.pop()

                if step_rows:
                    with obs.span("serve/step", batch=len(step_rows)):
                        self._step(step_rows, offload_log, finish)
                    n_steps += 1
                    progressed = True

                util = self.kv.utilization()
                peak_util = max(peak_util, util)
                frag_sum += self.kv.fragmentation()
                frag_n += 1
                if (idle is None and not progressed and not queue
                        and len(free_rows) == lc.max_batch):
                    idle = obs.span("serve/idle")
                    idle.__enter__()
                # yield so the producer can enqueue between steps
                await asyncio.sleep(0 if progressed else lc.idle_poll_s)
            await prod
        finally:
            if idle is not None:
                idle.__exit__(None, None, None)
            if not prod.done():
                prod.cancel()

        return ServeReport(
            records=[records[r.rid] for r in requests],
            duration_s=now(),
            n_prefills=n_prefills,
            n_steps=n_steps,
            preemptions=preemptions,
            leaked_pages=self.kv.allocated_pages,
            peak_utilization=peak_util,
            mean_fragmentation=frag_sum / max(frag_n, 1),
            offload=offload_log,
            speedup=lc.speedup,
        )

    def _step(self, step_rows, offload_log: list, finish) -> None:
        """One decode step of the live rows: price the offload, build the
        host index arrays, run the step, sample and advance every row."""
        lc = self.loop_cfg
        b = len(step_rows)
        with obs.span("serve/offload", batch=b):
            decision = self.scheduler.decide_decode(self.cfg, b)

        s_v = self._bucket(max(a.pos for a in step_rows))
        with obs.span("serve/decode/build", batch=b, view=s_v):
            token = np.zeros(lc.max_batch, np.int32)
            cache_pos = np.zeros(lc.max_batch, np.int32)
            gather_idx = np.full((lc.max_batch, s_v), self._pad_slot,
                                 np.int32)
            new_slots = np.full(lc.max_batch, self._pad_slot, np.int32)
            for a in step_rows:
                slots = self.kv.physical_slots(a.req.rid)
                gather_idx[a.row, :a.pos] = slots[:a.pos]
                new_slots[a.row] = slots[a.pos]
                token[a.row] = a.next_token
                cache_pos[a.row] = a.pos

        with obs.stopwatch("serve/decode", batch=b, view=s_v) as sw:
            logits, self.slab = self._decode_fn(
                self.params, self.slab, jnp.asarray(token),
                jnp.asarray(cache_pos), jnp.asarray(gather_idx),
                jnp.asarray(new_slots))
            logits_np = np.asarray(logits)
        self.scheduler.observe_host(b, sw.duration_s)
        offload_log.append({
            "batch": b,
            "target": decision.target,
            "modeled_s": decision.modeled_s,
            "host_ema_s": decision.host_s,
            "measured_s": sw.duration_s,
            "makespan_cycles": decision.price.makespan_cycles,
            "n_arrays": decision.price.n_arrays,
        })

        with obs.span("serve/sample", batch=b):
            next_tok = self._sample(logits_np)
            for a in step_rows:
                a.pos += 1
                t = int(next_tok[a.row])
                a.next_token = t
                a.generated.append(t)
                if len(a.generated) >= a.req.decode_len:
                    finish(a)

    def run_sync(self, requests) -> ServeReport:
        return asyncio.run(self.run(requests))

"""Runner of the serving cells: an open-loop request stream through the
program's ``ServeLoop`` (admission, continuous batching, paged KV cache).

Set-up: the model is the program's architecture at the configuration
file's sizes; its weights are drawn by the benchmark (``weights.py``). The
KV page pool fills the memory that weights and the largest programs leave:
``compiled.memory_analysis()`` of the loop's prefill at its largest bucket
and its decode step at its widest view, compiled through a one-page probe
loop, sizes it. ``ServeLoop.warmup`` then compiles every prefill bucket and
decode view the mix can reach, and no others.

Window: ``ServeLoop.run`` over the mix's requests (``traffic/gen.py``),
until every request due in the window has finished. Every request is
timed from its due time, not from when the producer got to it.

Check: a sample of finished requests drawn from the seed, the longest
among them, goes through ``reference/decoder.py`` over its prompt and its
served tokens; the number compared is the widest gap by which a served
token's reference logit lies below the reference's best at that position.
"""
from __future__ import annotations

import dataclasses
import sys
import time
from pathlib import Path

import numpy as np

HERE = Path(__file__).resolve().parents[1]
if str(HERE) not in sys.path:
    sys.path.insert(0, str(HERE))

import counts  # noqa: E402
import harness  # noqa: E402

log = harness.log

# the configuration file's keys, as the program's ArchConfig names them
ARCH_KEYS = {"num_hidden_layers": "num_layers", "hidden_size": "d_model",
             "num_attention_heads": "n_heads",
             "num_key_value_heads": "n_kv_heads", "head_dim": "head_dim",
             "intermediate_size": "d_ff", "vocab_size": "vocab_size",
             "rms_norm_eps": "norm_eps", "rope_theta": "rope_theta"}


def model_config(cfg: dict):
    """The program's architecture with the file's depth, rotary base, norm
    epsilon and embedding tie (options the program takes), checked against
    every other number of the file."""
    from repro.models.registry import get_config

    arch = dataclasses.replace(
        get_config(cfg["arch"]), num_layers=cfg["num_hidden_layers"],
        rope_theta=float(cfg["rope_theta"]),
        norm_eps=float(cfg["rms_norm_eps"]),
        tie_embeddings=bool(cfg["tie_word_embeddings"]),
        **cfg.get("runtime", {}))
    for k, a in ARCH_KEYS.items():
        if getattr(arch, a) != cfg[k]:
            raise RuntimeError(f"program's {a}={getattr(arch, a)!r} differs "
                               f"from the configuration's {k}={cfg[k]!r}")
    if arch.dtype != cfg["torch_dtype"]:
        raise RuntimeError("dtype differs from the file")
    if cfg["attention_bias"] or cfg["mlp_bias"]:
        raise RuntimeError("the program's projections have no biases")
    return arch


def pool_pages(arch, params, lc, max_prompt, max_view, device) -> int:
    """KV pages that fill what weights and the largest programs leave."""
    import jax
    import jax.numpy as jnp

    from repro.models.registry import get_module
    from repro.serve.loop import ServeLoop

    probe = ServeLoop(arch, params, dataclasses.replace(lc, num_pages=1))
    b = lc.max_batch
    ana = [
        probe._prefill_fn.lower(
            params, jax.ShapeDtypeStruct((1, max_prompt), jnp.int32),
            jnp.int32(0)).compile().memory_analysis(),
        probe._decode_fn.lower(
            params, probe.slab, jax.ShapeDtypeStruct((b,), jnp.int32),
            jax.ShapeDtypeStruct((b,), jnp.int32),
            jax.ShapeDtypeStruct((b, max_view), jnp.int32),
            jax.ShapeDtypeStruct((b,), jnp.int32)).compile()
        .memory_analysis(),
    ]
    need = max(a.temp_size_in_bytes + a.output_size_in_bytes
               - a.alias_size_in_bytes for a in ana)
    del probe
    weights = sum(x.nbytes for x in jax.tree.leaves(params))
    limit = device.memory_stats()["bytes_limit"]
    tmpl = jax.eval_shape(lambda: get_module(arch).init_cache(arch, 1, 1))
    per_token = sum(  # cache leaves are (G, B, S, Hkv, hd)
        leaf.shape[0] * leaf.shape[3] * leaf.shape[4] * leaf.dtype.itemsize
        for leaf in jax.tree.leaves(tmpl))
    free = limit - weights - need - HEADROOM_BYTES
    log(f"[setup] memory: limit {limit}, weights {weights}, largest "
        f"program {need}, {per_token} KV bytes per token")
    return max(1, free // (lc.page_size * per_token))


HEADROOM_BYTES = 512 * 2**20   # runtime, buffers in flight, fragmentation


def run(ctx) -> None:
    import jax

    from repro.models.registry import get_module
    from repro.serve.loop import ServeLoop, ServeLoopConfig

    import traffic.gen as gen
    import weights

    cfg, mix = ctx.config, ctx.traffic
    arch = model_config(cfg)
    t0 = time.perf_counter()
    shapes = jax.eval_shape(lambda k: get_module(arch).init(k, arch),
                            harness.jax_key(0))
    params = weights.draw(harness.jax_key(ctx.seed, 1), shapes)
    jax.block_until_ready(params)
    n_bytes = sum(x.nbytes for x in jax.tree.leaves(params))
    t1 = time.perf_counter()
    log(f"[setup] {cfg['name']}: {arch.num_layers} layers, weights "
        f"{n_bytes} bytes drawn in {t1 - t0:.3f} s")

    rng = harness.np_rng(ctx.seed, 2)
    requests = gen.generate(mix, rng, ctx.seconds, arch.vocab_size)
    max_prompt = int(mix["prompt"]["max"])
    max_new = int(mix["output"]["max"])
    loop_keys = mix["loop"]
    lc = ServeLoopConfig(
        max_batch=int(loop_keys["max_batch"]), num_pages=1,
        page_size=int(loop_keys["page_size"]),
        min_bucket=int(loop_keys["min_bucket"]),
        temperature=float(mix["temperature"]), speedup=1.0)
    prefill_max = lc.min_bucket
    while prefill_max < max_prompt:
        prefill_max *= 2
    view_max = lc.min_bucket
    while view_max < max_prompt + max_new:
        view_max *= 2
    lc = dataclasses.replace(lc, num_pages=pool_pages(
        arch, params, lc, prefill_max, view_max, ctx.devices[0]))
    loop = ServeLoop(arch, params, lc)
    t2 = time.perf_counter()
    n = loop.warmup(max_prompt, max_new)
    t3 = time.perf_counter()
    lens = np.array([r.prompt_len for r in requests])
    outs = np.array([r.decode_len for r in requests])
    log(f"[setup] pool {lc.num_pages} pages of {lc.page_size} "
        f"({t2 - t1:.3f} s); warm-up {n} programs {t3 - t2:.3f} s")
    log(f"[setup] traffic {ctx.spec.cell['traffic']}: {len(requests)} "
        f"requests, {mix['arrival']} at {mix['rate_rps']} req/s"
        f"; prompts mean {lens.mean():.1f} max {lens.max()}, outputs "
        f"mean {outs.mean():.1f} max {outs.max()}; batch {lc.max_batch}")

    ctx.window_start()
    report = loop.run_sync(requests)
    ctx.window_end()
    ctx.read_memory()
    summarize(ctx, requests, report)
    loop.slab = None
    del loop
    check(ctx, cfg, params, requests, report)


def pct(values, q: float) -> float:
    """Nearest-rank percentile: the smallest value with at least q% of
    the values at or below it."""
    v = np.sort(np.asarray(values, dtype=np.float64))
    if not len(v):
        return float("nan")
    return float(v[max(0, int(np.ceil(q / 100.0 * len(v))) - 1)])


def summarize(ctx, requests, report) -> None:
    recs = report.records
    by_id = {r.rid: r for r in requests}
    end = report.duration_s
    late = np.array([rec.arrival_s - by_id[rec.rid].arrival_s
                     for rec in recs if rec.arrival_s is not None])
    log(f"[window] {len(recs)} requests over {end:.3f} s; generator "
        f"lateness max {late.max() * 1e3:.3f} ms, p95 "
        f"{pct(late, 95) * 1e3:.3f} ms")
    dims = counts.model_dims(ctx.config)
    prefill_flops = 0
    ttft, tpot, queue = [], [], []
    finished = failed = 0
    for rec in recs:
        due = by_id[rec.rid].arrival_s
        if rec.first_token_s is not None:
            prefill_flops += counts.prefill_flops(rec.prompt_len, **dims)
            queue.append(rec.admitted_s - due)
        if rec.finished:
            finished += 1
            ttft.append(rec.first_token_s - due)
            if rec.n_generated > 1:
                tpot.append((rec.finished_s - rec.first_token_s)
                            / (rec.n_generated - 1))
        elif rec.rejected or rec.failed:
            failed += 1
            ttft.append(end - due)    # missing: it waited the whole run
    ctx.observed.update(n_prefills=report.n_prefills,
                        prefill_flops=prefill_flops, queue_s=queue)
    ctx.attempted = len(recs)
    ctx.end_to_end["ttft_p95_ms"] = 1e3 * pct(ttft, 95)
    ctx.end_to_end["tpot_p95_ms"] = 1e3 * pct(tpot, 95)
    log(f"[window] {finished}/{len(recs)} finished; ttft p50 "
        f"{1e3 * pct(ttft, 50):.3f} p95 {1e3 * pct(ttft, 95):.3f} ms; "
        f"tpot p50 {1e3 * pct(tpot, 50):.3f} p95 "
        f"{1e3 * pct(tpot, 95):.3f} ms; {report.n_steps} decode steps")
    ctx.failed = failed
    if report.leaked_pages:
        ctx.failed += 1
        log(f"[window] {report.leaked_pages} KV pages leaked")


def sample(ctx, requests, report):
    """Finished requests for the check: the one with the most tokens, then
    others in an order drawn from the seed, up to ``check_tokens`` served
    tokens."""
    done = [r for r in report.records if r.finished and r.tokens]
    by_id = {r.rid: r for r in requests}
    done.sort(key=lambda r: -(r.prompt_len + len(r.tokens)))
    rest = done[1:]
    order = harness.np_rng(ctx.seed, 3).permutation(len(rest))
    picked, served = done[:1], len(done[0].tokens) if done else 0
    budget = int(ctx.traffic["check_tokens"])
    for i in order:
        if served >= budget:
            break
        picked.append(rest[i])
        served += len(rest[i].tokens)
    return [(by_id[r.rid].prompt, np.asarray(r.tokens, np.int32))
            for r in picked]


def check(ctx, cfg, params, requests, report) -> None:
    import reference.decoder as ref

    picked = sample(ctx, requests, report)
    if not picked:
        ctx.compare("logit_gap", float("nan"), cfg["limits"]["logit_gap"])
        return
    seqs = [np.concatenate([p, t[:-1]]) for p, t in picked]
    positions = [np.arange(len(p) - 1, len(p) - 1 + len(t))
                 for p, t in picked]
    quants = (None, "fp8") if ctx.control else (None,)
    t0 = time.perf_counter()
    got = ref.logits_at(params, cfg, seqs, positions, quants)
    gap = gap_low = 0.0
    for i, (p, t) in enumerate(picked):
        lg = got[0][i]
        best = lg.max(axis=1)
        gap = max(gap, float((best - lg[np.arange(len(t)), t]).max()))
        if ctx.control:
            first = got[1][i].argmax(axis=1)
            gap_low = max(gap_low, float(
                (best - lg[np.arange(len(t)), first]).max()))
    n_tok = sum(len(t) for _, t in picked)
    log(f"[check] {len(picked)} requests, {n_tok} served tokens, "
        f"{sum(len(s) for s in seqs)} positions through the reference in "
        f"{time.perf_counter() - t0:.3f} s")
    ctx.compare("logit_gap", gap, cfg["limits"]["logit_gap"])
    if ctx.control:
        ctx.control.compare("logit_gap", gap_low, cfg["limits"]["logit_gap"])

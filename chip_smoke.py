#!/usr/bin/env python3
"""Bring-up smoke of the main path on a TPU, through the normal entry points.

    python chip_smoke.py              # phases A and B on one chip
    python chip_smoke.py --chips 4    # the mesh phase alone, on four chips

Phase A (the paper's path): a seeded sparse tensor with FROSTT nell-2's
dims (12,092 x 9,184 x 28,818) and 1/8 of its nonzeros, decomposed at rank
32 by three CP-ALS sweeps through ``backend="pallas"`` and again through
``backend="exact"`` from the same initial factors. Each mode's MTTKRP is
checked against ``"exact"`` within the pallas backend's documented
envelope, and the two final fits must agree (see ``FIT_TOL``).

Phase B (serving): granite_8b at its published widths (d_model 4096, 32
query and 8 KV heads of 128, d_ff 14336, vocab 49152, bf16) with depth cut
to 16 layers, random weights from a seed. Eight seeded requests of 256-1024
prompt tokens and 32 greedy new tokens go through ``ServeLoop``. Every
request must complete, no KV page may leak, and each request's first
token must equal the dense ``ServeEngine``'s first token for its prompt.

``--chips 4``: phase A's tensor, mode-0 MTTKRP on
``backends.get("psram-mesh", n_arrays=4)`` (eager lowering) against the
single-device ``"psram-stream"`` result, which it must match bit for bit.

Progress goes to stdout; the last line is one JSON object naming the
device. Anything but a TPU is refused with a nonzero exit: there is no CPU
or interpret-mode fallback.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import math
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
SEED = 0

NELL2_DIMS = (12092, 9184, 28818)
NELL2_NNZ = 76_879_419
NNZ_CUT = 8                 # phase A streams nell-2's nnz / 8
RANK = 32
SWEEPS = 3
# |fit_pallas - fit_exact| bound: fit = 1 - ||X - Xhat|| / ||X||, and the
# pallas factor updates see MTTKRP within a relative 0.05 of exact (the
# backend's rel_tol), so Xhat, and with it the residual norm, moves by at
# most ~5% of ||Xhat|| <= ||X||: the fits may differ by up to 0.05.
FIT_TOL = 0.05

SERVE_LAYERS = 16           # of granite_8b's 36
N_REQUESTS = 8
PROMPT_LO, PROMPT_HI = 256, 1024
NEW_TOKENS = 32
PAGE_SIZE = 16
NUM_PAGES = 2048            # 32,768 token slots: 2.1 GB of bf16 KV at 16 layers


def log(msg: str) -> None:
    print(msg, flush=True)


def nell2_tensor(nnz: int, seed: int = SEED):
    """Seeded nell-2-shaped tensor and its three mode-rooted CSFs."""
    import jax

    from repro.sparse import csf_for_mode, powerlaw_coo

    t0 = time.perf_counter()
    coo = powerlaw_coo(jax.random.PRNGKey(seed), NELL2_DIMS, nnz=nnz,
                       rank=RANK)
    t1 = time.perf_counter()
    csfs = [csf_for_mode(coo, m) for m in range(len(NELL2_DIMS))]
    t2 = time.perf_counter()
    log(f"[setup] tensor {NELL2_DIMS} nnz={coo.nnz} generated in "
        f"{t1 - t0:.3f}s (host)")
    log(f"[setup] 3 CSFs built in {t2 - t1:.3f}s (host)")
    return coo, csfs


def phase_decomposition(nnz: int, sweeps: int = SWEEPS) -> None:
    import jax
    import jax.numpy as jnp

    from repro import backends
    from repro.core.cp_als import cp_als, init_factors

    log(f"[A] cut: nnz {nnz} = nell-2's {NELL2_NNZ} / {NNZ_CUT} "
        f"(dims and rank {RANK} as published)")
    coo, csfs = nell2_tensor(nnz)
    pallas = backends.get("pallas")
    for op, low in pallas.lowerings.items():
        log(f"[A] lowering {op}: {low}")
        if low in ("interpret", "ref"):
            raise RuntimeError(f"{op} resolved to {low!r} on the chip")

    exact = backends.get("exact")
    tol = pallas.capabilities().rel_tol
    factors = init_factors(jax.random.PRNGKey(SEED), NELL2_DIMS, RANK)
    for mode in range(len(NELL2_DIMS)):
        got = pallas.mttkrp(csfs[mode], factors, mode)
        want = exact.mttkrp(csfs[mode], factors, mode)
        rel = float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))
        ok = bool(jnp.all(jnp.isfinite(got))) and rel < tol
        log(f"[A] mode-{mode} MTTKRP {tuple(got.shape)} pallas vs exact: "
            f"rel={rel!r} (< {tol}) {'ok' if ok else 'FAIL'}")
        if not ok:
            raise RuntimeError(f"mode-{mode} MTTKRP outside the envelope")

    fits, states = {}, {}
    for name in ("pallas", "exact"):
        kw = dict(sparse=coo, csfs=csfs, backend=name,
                  key=jax.random.PRNGKey(SEED), tol=0.0)
        t0 = time.perf_counter()
        cp_als(None, RANK, n_iter=1, **kw)
        t1 = time.perf_counter()
        st = cp_als(None, RANK, n_iter=sweeps, **kw)
        t2 = time.perf_counter()
        fits[name], states[name] = st.fit, st
        log(f"[A] cp_als backend={name}: first sweep (compile + layout) "
            f"{t1 - t0:.3f}s, then {st.iters} sweeps {t2 - t1:.3f}s "
            f"({(t2 - t1) / st.iters:.3f}s/sweep), fit={st.fit!r}")
    for m, (fp, fe) in enumerate(zip(states["pallas"].factors,
                                     states["exact"].factors)):
        rel = float(jnp.linalg.norm(fp - fe) / jnp.linalg.norm(fe))
        log(f"[A] final factor {m} pallas vs exact: rel={rel!r}")
    diff = abs(fits["pallas"] - fits["exact"])
    ok = all(math.isfinite(f) for f in fits.values()) and diff <= FIT_TOL
    log(f"[A] fit pallas {fits['pallas']!r} vs exact {fits['exact']!r}: "
        f"|diff|={diff!r} (<= {FIT_TOL}) {'ok' if ok else 'FAIL'}")
    if not ok:
        raise RuntimeError("CP-ALS fits disagree")
    log("[A] PASS")


def serve_config(layers: int = SERVE_LAYERS):
    from repro.models.registry import get_config

    return dataclasses.replace(get_config("granite_8b"), num_layers=layers)


def seeded_requests(cfg, n=N_REQUESTS, lo=PROMPT_LO, hi=PROMPT_HI,
                    new_tokens=NEW_TOKENS, seed=SEED):
    import numpy as np

    from repro.serve.traffic import Request

    rng = np.random.default_rng(seed)
    lens = rng.integers(lo, hi + 1, size=n)
    return [Request(rid=i, arrival_s=0.0,
                    prompt=rng.integers(0, cfg.vocab_size, size=int(p),
                                        dtype=np.int32),
                    decode_len=new_tokens)
            for i, p in enumerate(lens)]


def phase_serving(cfg, requests, num_pages: int = NUM_PAGES,
                  min_bucket: int = 256) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.models.registry import get_module
    from repro.serve import ServeEngine
    from repro.serve.loop import ServeLoop, ServeLoopConfig

    log(f"[B] cut: granite_8b depth {cfg.num_layers} of 36 layers; widths "
        f"as published (d_model {cfg.d_model}, heads {cfg.n_heads}/"
        f"{cfg.n_kv_heads}x{cfg.head_dim}, d_ff {cfg.d_ff}, vocab "
        f"{cfg.vocab_size}, {cfg.dtype})")
    t0 = time.perf_counter()
    params = get_module(cfg).init(jax.random.PRNGKey(SEED), cfg)
    jax.block_until_ready(params)
    n_bytes = sum(x.nbytes for x in jax.tree.leaves(params))
    log(f"[B] weights {n_bytes / 1e9:.3f} GB drawn in "
        f"{time.perf_counter() - t0:.3f}s")

    max_prompt = max(r.prompt_len for r in requests)
    max_new = max(r.decode_len for r in requests)
    loop = ServeLoop(cfg, params, ServeLoopConfig(
        max_batch=len(requests), num_pages=num_pages, page_size=PAGE_SIZE,
        min_bucket=min_bucket, speedup=1.0))
    t0 = time.perf_counter()
    n = loop.warmup(max_prompt, max_new)
    log(f"[B] warmup: {n} compiled calls in {time.perf_counter() - t0:.3f}s")
    report = loop.run_sync(requests)
    s = report.summary()
    log(f"[B] served {s['completed']}/{len(requests)} requests in "
        f"{s['duration_s']:.3f}s: {report.n_prefills} prefills, "
        f"{report.n_steps} decode steps, {s['throughput_tok_s']:.3f} tok/s, "
        f"leaked_pages={s['leaked_pages']}, preemptions={s['preemptions']}")
    ok = (s["completed"] == len(requests) and not s["rejected"]
          and not s["failed"] and s["leaked_pages"] == 0
          and all(r.n_generated == q.decode_len
                  for r, q in zip(report.records, requests)))
    if not ok:
        raise RuntimeError(f"serve loop did not complete cleanly: {s}")

    # The logits come out of a bf16 unembed matmul, so they sit on the bf16
    # grid: at |logit| in [4, 8) one step is 2^-5, and ties among 49,152
    # logits are common. The loop prefills a prompt padded to its bucket,
    # the engine the bare prompt, and the two shapes may round a logit one
    # bf16 step apart. So the loop's first token must be the engine's, or,
    # where the engine's top logits are that close, one whose engine logit
    # is within one bf16 step of the engine's maximum.
    engine = ServeEngine(cfg, params, max_len=max_prompt + 1)
    exact = near = 0
    for rec, req in zip(report.records, requests):
        logits, _ = engine.prefill_fn(params, jnp.asarray(req.prompt)[None])
        ref = np.asarray(logits[0], np.float32)
        want, got = int(np.argmax(ref)), rec.tokens[0]
        top = float(ref[want])
        step = float(jnp.finfo(jnp.bfloat16).eps
                     * 2.0 ** np.floor(np.log2(abs(top))))
        gap = top - float(ref[got])
        exact += got == want
        near += got != want and gap <= step
        verdict = ("ok" if got == want else
                   "ok, within one bf16 step" if gap <= step else "FAIL")
        log(f"[B] request {rec.rid} prompt {req.prompt_len}: first token "
            f"loop {got} vs engine {want}, engine logit gap {gap!r} "
            f"(bf16 step {step!r}) {verdict}")
    log(f"[B] first tokens: {exact} equal, {near} within one bf16 step of "
        f"the engine's maximum, of {len(requests)}")
    if exact + near != len(requests):
        raise RuntimeError("first tokens differ from the dense engine")
    log("[B] PASS")


def phase_mesh(nnz: int, n_arrays: int = 4) -> None:
    import jax
    import numpy as np

    from repro import backends, obs
    from repro.core.cp_als import init_factors

    log(f"[mesh] cut: nnz {nnz} = nell-2's {NELL2_NNZ} / {NNZ_CUT}")
    _, csfs = nell2_tensor(nnz)
    factors = init_factors(jax.random.PRNGKey(SEED), NELL2_DIMS, RANK)
    mesh_be = backends.get("psram-mesh", n_arrays=n_arrays)
    obs.enable()            # the per-shard plan spans name each shard's device
    t0 = time.perf_counter()
    got = jax.block_until_ready(mesh_be.mttkrp(csfs[0], factors, 0))
    t1 = time.perf_counter()
    obs.disable()
    want = jax.block_until_ready(
        backends.get("psram-stream").mttkrp(csfs[0], factors, 0))
    t2 = time.perf_counter()
    log(f"[mesh] psram-mesh x{n_arrays} {t1 - t0:.3f}s, psram-stream "
        f"{t2 - t1:.3f}s (first calls, compile included)")
    placed = {ev["name"]: ev["args"] for ev in obs.get_tracer().events()
              if ev["name"].endswith("/plan")}
    for name, a in sorted(placed.items()):
        log(f"[mesh] {name}: nnz {a['nnz']} on {a['device']}")
    distinct = {a["device"] for a in placed.values()
                if a["device"].startswith("tpu:")}
    if len(placed) != n_arrays or len(distinct) != n_arrays:
        raise RuntimeError(f"shards sit on {len(distinct)} distinct TPU "
                           f"devices, not {n_arrays}")
    same = np.array_equal(np.asarray(got), np.asarray(want))
    log(f"[mesh] mode-0 MTTKRP {tuple(got.shape)} bit-identical to "
        f"psram-stream: {same}")
    if not same:
        raise RuntimeError("mesh result differs from the single-device "
                           "stream")
    log("[mesh] PASS")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs the mesh phase alone, on four chips")
    args = ap.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        print(f"chip_smoke: no repro package under {ROOT / 'src'}; run this "
              "script from a checkout of the repository", file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))

    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU found: JAX's devices are {len(devices)} "
              f"x {dev.platform} ({dev.device_kind}); this smoke runs only "
              "on a TPU, with no CPU or interpret-mode fallback",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} TPU "
              f"devices, found {len(devices)}", file=sys.stderr)
        return 1

    from repro.launch.compile_cache import enable_compile_cache

    log(f"[setup] device {dev.platform} {dev.device_kind} x{len(devices)}; "
        f"compile cache {enable_compile_cache()}")
    nnz = NELL2_NNZ // NNZ_CUT
    if args.chips == 4:
        phase_mesh(nnz, n_arrays=4)
    else:
        phase_decomposition(nnz)
        cfg = serve_config()
        phase_serving(cfg, seeded_requests(cfg))
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

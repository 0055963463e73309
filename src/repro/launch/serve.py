"""Serving launcher: batched request demo against any arch (reduced on CPU)."""
from __future__ import annotations

import argparse


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--max-new", type=int, default=32)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--model-parallel", type=int, default=0,
                    help="build a (data, model) host mesh with this model-"
                         "axis size and serve under use_sharding")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    from repro.launch.compile_cache import enable_compile_cache
    from repro.models.registry import get_config, get_module
    from repro.serve import ServeEngine

    enable_compile_cache()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    mod = get_module(cfg)
    params = mod.init(jax.random.PRNGKey(0), cfg)
    mesh = None
    if args.model_parallel:
        from repro.launch.mesh import make_host_mesh
        mesh = make_host_mesh(model=args.model_parallel)
    eng = ServeEngine(cfg, params, max_len=args.prompt_len + args.max_new,
                      mesh=mesh)
    prompts = jax.random.randint(
        jax.random.PRNGKey(1), (args.batch, args.prompt_len), 2, cfg.vocab_size
    ).astype(jnp.int32)
    kwargs = {}
    if cfg.family == "encdec":
        kwargs["frames"] = jax.random.normal(
            jax.random.PRNGKey(2), (args.batch, args.prompt_len * 4, cfg.d_model),
            dtype=jnp.dtype(cfg.dtype))
    from repro import obs

    # the obs stopwatch owns the measurement: the printed tok/s summary is
    # sourced from it, and a "serve/generate" span lands in the trace
    # whenever tracing is on
    with obs.stopwatch("serve/generate", batch=args.batch,
                       max_new=args.max_new, arch=args.arch) as sw:
        toks = eng.generate(prompts, args.prompt_len, args.max_new,
                            temperature=args.temperature,
                            key=jax.random.PRNGKey(3), **kwargs)
    dt = sw.duration_s
    total = args.batch * args.max_new
    print(f"generated {toks.shape} in {dt:.2f}s  ({total/dt:.1f} tok/s batched)")
    print("sample:", toks[0][:16].tolist())


if __name__ == "__main__":
    main()

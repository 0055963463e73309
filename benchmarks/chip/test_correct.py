"""``correct`` comes out false when the timed path is broken underneath.

Each test skips the harness's look for a chip, drives the rest of a run on
the CPU at a tiny size with one fault planted in the program's path, and
sees the comparison fail; and the control (the reference one precision
below the configuration's), put through the same comparison against the
same limits, comes out not correct where the program comes out correct.
The control is read at the cells' own sizes on the chip by
``control.py``; PERF.md gives those readings.
"""
import jax.numpy as jnp
import pytest

import harness

# ----------------------------------------------------------------- CP-ALS


def _wrap_mttkrp(monkeypatch, wrap):
    mod = harness.load_module(harness.HERE / "systems" / "cpals.py",
                              "bench_system_cpals")
    orig = mod.make_backend

    def make_backend(ctx):
        be = orig(ctx)
        be.mttkrp = wrap(be.mttkrp)
        return be

    monkeypatch.setattr(mod, "make_backend", make_backend)


def _state_unchanged(fn):
    """The sweep's update gives the mode its old factor back."""
    def f(data, factors, mode):
        h = None
        for d, g in enumerate(factors):
            if d != mode:
                h = g.T @ g if h is None else h * (g.T @ g)
        return factors[mode] @ h
    return f


def _half_left_out(fn):
    """Every other nonzero dropped, the sum of the rest doubled."""
    from repro.sparse.formats import COO, csf_for_mode

    halves = {}

    def f(data, factors, mode):
        if mode not in halves:
            c = data.to_coo()
            halves[mode] = csf_for_mode(
                COO(c.indices[::2], c.values[::2], c.shape), mode)
        return 2.0 * fn(halves[mode], factors, mode)
    return f


def _answer_altered(fn):
    """One output row of the MTTKRP changed where it is produced."""
    def f(data, factors, mode):
        out = fn(data, factors, mode)
        return out.at[0].add(jnp.linalg.norm(out))
    return f


def test_cpals_program_passes_and_control_fails(tiny):
    ctx = tiny("nell2.cpals", 5)
    ctx.control = harness.Checks()
    harness.finish(ctx)
    assert ctx.correct, ctx.compared
    assert not ctx.control.correct, ctx.control.compared


@pytest.mark.parametrize("fault", [_state_unchanged, _half_left_out,
                                   _answer_altered])
def test_cpals_fault_is_not_correct(tiny, monkeypatch, fault):
    _wrap_mttkrp(monkeypatch, fault)
    ctx = tiny("nell2.cpals", 6)
    harness.finish(ctx)
    assert not ctx.correct, ctx.compared


# ---------------------------------------------------------------- serving


def _patch_loop(monkeypatch, after_init=None, sample=None):
    from repro.serve import loop as loop_mod

    if after_init is not None:
        init = loop_mod.ServeLoop.__init__

        def patched(self, *a, **k):
            init(self, *a, **k)
            if self.loop_cfg.num_pages > 1:    # not the pool-sizing probe
                after_init(self)

        monkeypatch.setattr(loop_mod.ServeLoop, "__init__", patched)
    if sample is not None:
        monkeypatch.setattr(loop_mod.ServeLoop, "_sample",
                            sample(loop_mod.ServeLoop._sample))


def _token_altered(monkeypatch):
    calls = [0]

    def sample(orig):
        def f(self, logits):
            tok = orig(self, logits)
            calls[0] += 1
            if calls[0] % 4 == 0:
                tok = (tok + 1) % logits.shape[-1]
            return tok
        return f

    _patch_loop(monkeypatch, sample=sample)


def _half_batch_left_out(monkeypatch):
    def after(loop):
        fn = loop._decode_fn
        half = loop.loop_cfg.max_batch // 2

        def decode(*a):
            logits, slab = fn(*a)
            return logits.at[half:].set(logits[:1]), slab
        loop._decode_fn = decode

    _patch_loop(monkeypatch, after_init=after)


def _state_unchanged_serve(monkeypatch):
    """Prefill's KV never reaches the page pool."""
    def after(loop):
        loop._scatter_fn = lambda slab, caches, slots: slab

    _patch_loop(monkeypatch, after_init=after)


@pytest.mark.parametrize("workload", ["granite8b.code"])
def test_serve_program_passes(tiny, workload):
    ctx = tiny(workload, 7, seconds=2.0)
    harness.finish(ctx)
    assert ctx.correct, ctx.compared


def test_serve_control_fails(tiny):
    ctx = tiny("granite8b.code", 9, seconds=2.0)
    ctx.control = harness.Checks()
    harness.finish(ctx)
    assert ctx.correct, ctx.compared
    assert not ctx.control.correct, ctx.control.compared


@pytest.mark.parametrize("fault", [_token_altered, _half_batch_left_out,
                                   _state_unchanged_serve])
def test_serve_fault_is_not_correct(tiny, monkeypatch, fault):
    fault(monkeypatch)
    ctx = tiny("granite8b.code", 8, seconds=1.0)
    harness.finish(ctx)
    assert not ctx.correct, ctx.compared

"""Kernel lowering selection — the registry-owned home of the strings that
used to live privately in ``kernels.ops``.

The Pallas ops pick between lowerings of the same kernel body:

* ``"pallas"``    — real Pallas lowering (TPU).
* ``"interpret"`` — the same kernel body, Python-executed (CPU validation).
* ``"xla"``       — the same body as one fused jit (``lax.scan`` chunk walk
  for the streaming kernel): the fast lowering off-TPU, where interpret
  mode is orders of magnitude too slow to race.
* ``"ref"``       — the pure-jnp oracle from ``kernels.ref``.

``"auto"`` resolves by an env/platform probe done ONCE per process (the
probe result is cached; backends resolve at *construction*, not per call):

* :func:`resolve_lowering` — the validation contract: Pallas on TPU,
  interpret elsewhere. What the per-op kernel wrappers default to.
* :func:`resolve_exec_lowering` — the execution contract of the fused
  (``compiled=True``) paths: Pallas on TPU, XLA elsewhere.
* :func:`resolve_stream_lowering` — the fused streaming MTTKRP alone: XLA
  on every platform, TPU included (see its docstring for why).

``REPRO_KERNEL_LOWERING`` overrides what ``"auto"`` resolves to in both
(e.g. ``=interpret`` to force kernel-body validation everywhere). Before
this module, an unknown string silently fell through to the Pallas path;
now it raises with the valid set, and the registry's ``"pallas"`` backend
and ``kernels.ops`` share one resolver.
"""
from __future__ import annotations

import functools
import os

import jax

KERNEL_LOWERINGS = ("auto", "pallas", "interpret", "xla", "ref")
#: the resolved (executable) subset — what a resolver may return
RESOLVED_LOWERINGS = ("pallas", "interpret", "xla", "ref")


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


@functools.lru_cache(maxsize=None)
def _env_override() -> str | None:
    """The one-time env probe: ``REPRO_KERNEL_LOWERING`` names a resolved
    lowering that ``"auto"`` maps to, for both contracts."""
    env = os.environ.get("REPRO_KERNEL_LOWERING", "").strip().lower()
    if not env:
        return None
    if env not in RESOLVED_LOWERINGS:
        raise ValueError(
            f"REPRO_KERNEL_LOWERING={env!r} is not a resolved lowering; "
            f"valid: {', '.join(RESOLVED_LOWERINGS)}"
        )
    return env


def _validate(backend: str) -> None:
    if backend not in KERNEL_LOWERINGS:
        raise ValueError(
            f"unknown kernel lowering {backend!r}; valid: "
            f"{', '.join(KERNEL_LOWERINGS)}"
        )


def resolve_lowering(backend: str = "auto") -> str:
    """Resolve a kernel-op ``backend`` string for the *validation* contract
    (``"auto"`` picks Pallas on TPU, interpret elsewhere — the per-op
    kernels' bit-identical-body path)."""
    _validate(backend)
    if backend == "auto":
        return _env_override() or ("pallas" if on_tpu() else "interpret")
    return backend


def resolve_exec_lowering(backend: str = "auto") -> str:
    """Resolve for the *execution* contract of the fused kernel family
    (``"auto"`` picks Pallas on TPU, the fused XLA lowering elsewhere —
    the path that has to win benchmarks, not just validate)."""
    _validate(backend)
    if backend == "auto":
        return _env_override() or ("pallas" if on_tpu() else "xla")
    return backend


def resolve_stream_lowering(backend: str = "auto") -> str:
    """Resolve for the fused streaming sparse MTTKRP
    (``kernels.stream_mttkrp``): ``"auto"`` picks the ``"xla"`` lowering on
    every platform, TPU included.

    The Pallas lowering of that kernel does not compile for TPU v5e
    (jax 0.9.0 / libtpu 0.0.34). Its per-chunk segment-pointer block
    ``(1, E*n_seg)`` of the ``(nb, E*n_seg)`` array breaks the tiling rule
    ("the last two dimensions of your block shape are divisible by 8 and
    128 respectively, or be equal to the respective dimensions of the
    overall array"). With that block made whole, Mosaic refuses the
    data-dependent factor-row gather ``ps[d][idx]`` inside the kernel body
    ("Shape mismatch in input, indices and output"), and the scatter
    ``out_ref[...].at[sp].add`` is of the same kind. The ``"xla"`` lowering
    runs the same ``_chunk_partials`` body as a ``lax.scan`` and compiles
    for the chip. An explicit ``"pallas"`` request is passed through and
    fails loudly at compile time; nothing falls back at run time.
    """
    _validate(backend)
    if backend == "auto":
        return _env_override() or "xla"
    return backend

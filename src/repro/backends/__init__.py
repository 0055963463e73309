"""repro.backends — the unified backend registry.

One :class:`Backend` protocol (``mttkrp`` / ``matmul`` / ``cost`` /
``capabilities``), one registry (:func:`register` / :func:`get` /
:func:`list_backends`), and first-class implementations wrapping every
execution path in the repo: ``"exact"``, ``"psram-oracle"``,
``"psram-scheduled"``, ``"psram-stream"``, ``"pallas"``, and the cost-only
``"analytical"``. ``repro.api`` is the thin facade on top; ``cp_als``,
``serve.offload_report``, the benchmarks, and the examples all dispatch by
registry name. :func:`resolve_config` is the single place a missing
``PsramConfig`` defaults to the paper's §V-A operating point and is
validated.
"""
from .base import (
    Backend,
    BackendError,
    Capabilities,
    CapabilityError,
    Estimate,
    UnknownBackendError,
    get,
    list_backends,
    register,
    resolve_config,
)
from .lowering import (
    KERNEL_LOWERINGS,
    RESOLVED_LOWERINGS,
    resolve_exec_lowering,
    resolve_lowering,
    resolve_stream_lowering,
)
from .workload import (
    MatmulWorkload,
    MTTKRPProblem,
    describe,
    normalize_mttkrp_data,
)

__all__ = [
    "Backend",
    "BackendError",
    "Capabilities",
    "CapabilityError",
    "Estimate",
    "KERNEL_LOWERINGS",
    "MatmulWorkload",
    "MTTKRPProblem",
    "UnknownBackendError",
    "describe",
    "get",
    "list_backends",
    "normalize_mttkrp_data",
    "RESOLVED_LOWERINGS",
    "register",
    "resolve_config",
    "resolve_exec_lowering",
    "resolve_lowering",
    "resolve_stream_lowering",
]

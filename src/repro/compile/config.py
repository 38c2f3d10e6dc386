"""``LoweringConfig``: the backend/dispatch handle threaded through models
and engines, replacing the old ``models.layers`` module-global impl flags.

Environment overrides (``REPRO_ATTENTION_IMPL``, falling back to
``REPRO_BACKEND``) are read in exactly one place — this constructor — and
only when no explicit backend is given.  With neither set, the platform
decides: compiled Pallas kernels on a TPU, the XLA reference elsewhere.
Everything downstream (layers, model families, serve engines, launchers)
receives the object; nothing else consults ``os.environ``.
"""

from __future__ import annotations

import os
from typing import Optional

import jax

from repro.compile.dispatch import CompileRecord, Dispatcher, get_dispatcher
from repro.compile.trace import OpKey
from repro.kernels import ref as kref
from repro.pointcloud import ref as pcref
from repro.targets.registry import TargetRegistry

VALID_BACKENDS = ("xla", "xla_chunked", "pallas", "pallas_interpret")

#: First env var set wins; read only by the LoweringConfig constructor.
_ENV_VARS = ("REPRO_ATTENTION_IMPL", "REPRO_BACKEND")


class LoweringConfig:
    """Per-model/engine lowering policy.

    backend:
      'xla'              — reference jnp lowering everywhere (default off
                           a TPU)
      'xla_chunked'      — online-softmax chunked attention in pure XLA
      'pallas'           — compiled Pallas ISAX kernels (default on a TPU)
      'pallas_interpret' — Pallas kernel bodies in interpret mode (CPU
                           tests; refused on a TPU)

    The backend states a *preference*; the dispatcher still decides per
    (op, shape, dtype) whether the e-graph pipeline matched an ISAX and
    whether the synthesis schedule is feasible, falling back to the XLA
    reference otherwise.
    """

    def __init__(self, backend: Optional[str] = None,
                 dispatcher: Optional[Dispatcher] = None):
        if backend is None:
            for name in _ENV_VARS:
                backend = os.environ.get(name)
                if backend:
                    break
        on_tpu = jax.default_backend() == "tpu"
        if not backend:
            backend = "pallas" if on_tpu else "xla"
        if backend not in VALID_BACKENDS:
            raise ValueError(f"unknown backend {backend!r}; "
                             f"valid: {VALID_BACKENDS}")
        if backend == "pallas_interpret" and on_tpu:
            raise ValueError("backend 'pallas_interpret' runs the kernels in "
                             "the CPU interpreter; on a TPU use 'pallas'")
        self.backend = backend
        self.interpret = backend == "pallas_interpret"
        self.dispatcher = dispatcher or get_dispatcher()

    @classmethod
    def from_registry(cls, backend: Optional[str] = None, *,
                      registry: Optional[TargetRegistry] = None,
                      dispatcher: Optional[Dispatcher] = None
                      ) -> "LoweringConfig":
        """Build a lowering policy over an ISAX/domain registry.

        The canonical constructor for engines, launchers, examples, and
        benchmarks: with no arguments it binds the global ``repro.targets``
        registry through the process-wide compile cache; pass ``registry=``
        to dispatch against an isolated :class:`TargetRegistry` (e.g. one
        carrying an experimental domain) with its own fresh cache, or
        ``dispatcher=`` to share a specific cache instance.
        """
        if dispatcher is None:
            dispatcher = (Dispatcher(registry) if registry is not None
                          else get_dispatcher())
        elif registry is not None and dispatcher.registry is not registry:
            raise ValueError("pass either registry= or dispatcher=, not "
                             "disagreeing both")
        return cls(backend=backend, dispatcher=dispatcher)

    @property
    def registry(self) -> TargetRegistry:
        """The ISAX/domain registry this policy dispatches against."""
        return self.dispatcher.registry

    def __repr__(self):
        return f"LoweringConfig(backend={self.backend!r})"

    def lower(self, op: str, shape, dtype) -> CompileRecord:
        """Compile-cache lookup for one op instance (called at trace time)."""
        return self.dispatcher.lower(
            OpKey(op, tuple(int(s) for s in shape), str(dtype), self.backend))

    # -- standalone op entry points (ops with no models/ host function) ----

    def int8_matmul(self, x, wq, scale):
        """Quantized GEMM through the dispatcher: x (M,K) float, wq (N,K)
        int8, scale (N,) → (M,N)."""
        M, K = x.shape
        N = wq.shape[0]
        rec = self.lower("int8_matmul", (M, K, N), x.dtype)
        if rec.impl == "isax":
            return rec.kernel_fn(x, wq, scale, interpret=self.interpret)
        return kref.int8_matmul_ref(x, wq, scale)

    # -- point-cloud vertical (fps → ball_query → group_aggregate) ---------

    def fps(self, xyz, n_samples: int):
        """Farthest-point sampling through the dispatcher: xyz (B,N,d) →
        sampled indices (B, n_samples) i32."""
        B, N, _ = xyz.shape
        rec = self.lower("fps", (B, N, n_samples), xyz.dtype)
        if rec.impl == "isax":
            return rec.kernel_fn(xyz, n_samples, interpret=self.interpret)
        return pcref.fps_ref(xyz, n_samples)

    def ball_query(self, xyz, centers, radius: float, k: int):
        """Ball-query grouping through the dispatcher: xyz (B,N,d),
        centers (B,M,d) → neighbor indices (B,M,k) i32."""
        B, N, _ = xyz.shape
        M = centers.shape[1]
        rec = self.lower("ball_query", (B, N, M, k), xyz.dtype)
        if rec.impl == "isax":
            return rec.kernel_fn(xyz, centers, radius, k,
                                 interpret=self.interpret)
        return pcref.ball_query_ref(xyz, centers, radius, k)

    def group_aggregate(self, features, idx):
        """Grouped feature aggregation through the dispatcher: features
        (B,N,C), idx (B,M,k) → max-pooled (B,M,C)."""
        B, N, C = features.shape
        M, k = idx.shape[1], idx.shape[2]
        rec = self.lower("group_aggregate", (B, N, M, k, C), features.dtype)
        if rec.impl == "isax":
            return rec.kernel_fn(features, idx, interpret=self.interpret)
        return pcref.group_aggregate_ref(features, idx)


# ---------------------------------------------------------------------------
# Process default (what model functions use when no LoweringConfig is
# threaded in — e.g. the trainer and the dry-run launcher).
# ---------------------------------------------------------------------------

_DEFAULT: Optional[LoweringConfig] = None


def default_lowering() -> LoweringConfig:
    """The process-default LoweringConfig (created lazily from the env,
    bound to the global ``repro.targets`` registry)."""
    global _DEFAULT
    if _DEFAULT is None:
        _DEFAULT = LoweringConfig.from_registry()
    return _DEFAULT


def lower(op: str, *, shape, dtype, backend: Optional[str] = None
          ) -> CompileRecord:
    """Public one-shot lowering: compile-cache lookup for one op instance
    through the registry-backed dispatch pipeline.

    The top-level entry point of the retargetable lowering API:
    ``repro.compile.lower("attention", shape=(1, 128, 4, 2, 128, 64),
    dtype="float32", backend="pallas")``.  With ``backend=None`` the
    process-default policy (env override included) applies; an explicit
    ``backend`` reuses the default policy's dispatcher (and therefore its
    registry and compile cache), so repeated calls are O(dict lookup) and
    a custom default installed via ``set_default_lowering`` keeps working.
    """
    dflt = default_lowering()
    if backend is None:
        return dflt.lower(op, shape, dtype)
    return LoweringConfig(backend=backend,
                          dispatcher=dflt.dispatcher).lower(op, shape, dtype)


def set_default_lowering(lowering: LoweringConfig) -> Optional[LoweringConfig]:
    """Install a new process-default; returns the prior one (for restore)."""
    global _DEFAULT
    prior = _DEFAULT
    _DEFAULT = lowering
    return prior


def set_default_backend(backend: str) -> str:
    """Launcher convenience: swap the default backend, returning the prior
    backend name.  Note jit caches traces — changing the default does not
    retrace already-compiled functions (same as the old global flag)."""
    prior = default_lowering().backend
    set_default_lowering(LoweringConfig.from_registry(backend))
    return prior


def get_default_backend() -> str:
    """Backend name of the process-default LoweringConfig."""
    return default_lowering().backend

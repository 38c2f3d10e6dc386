"""Compile-cache keys and registry-backed trace lookups.

This is the front half of the dispatch pipeline (trace → saturate → match →
extract → kernel).  The *trace programs themselves* — the deliberately
divergent software-side loop nests for every hot op — live on the
``repro.targets`` domain packages now (``IsaxSpec.trace_program``); this
module keeps the cache key (:class:`OpKey`) and thin registry-backed
views so historical imports (``TARGET_ISAX``, ``trace_kind``,
``trace_term``) keep working and can never drift from the registry.

``OpKey`` is the compile-cache key: one entry per (op, shape, dtype,
backend).  Shape tuples are per-op conventions (documented on ``OpKey``)
chosen so that every distinct kernel-schedule decision gets its own entry
while batch-irrelevant details are folded away.  Op names are validated
against the dispatcher's registry at lowering time (not here), so keys for
custom-registry domains construct cleanly.
"""

from __future__ import annotations

import dataclasses
from collections.abc import Mapping

from repro.core.expr import Term
from repro.targets import default_registry


class _TargetIsaxView(Mapping):
    """Live ``op → target-ISAX-or-None`` mapping over the global registry.

    Replaces the old hand-maintained module dict: iteration order is
    registration order, membership tracks whatever domains are registered,
    and a ``None`` value still marks a deliberate negative control."""

    def __getitem__(self, op: str):
        return default_registry().target_isax(op)

    def __iter__(self):
        return iter(default_registry().ops())

    def __len__(self):
        return len(default_registry().ops())

    def __repr__(self):
        return f"TARGET_ISAX({dict(self)!r})"


#: op name → the ISAX the compiler is expected to be able to target (None
#: means "no specialized datapath exists" — a deliberate negative control
#: whose keys must lower to the XLA reference).  Derived live from the
#: ``repro.targets`` registry.
TARGET_ISAX: Mapping = _TargetIsaxView()


@dataclasses.dataclass(frozen=True)
class OpKey:
    """Compile-cache key: one persistent entry per (op, shape, dtype, backend).

    Shape conventions (built-in domains):
      attention / attention_decode / attention_paged / attention_causal:
                   (B, S, H, K, T, hd)
      rmsnorm:     (rows, d)
      matmul:      (rows, d_in, d_out)
      int8_matmul: (rows, d_in, d_out)
      ssd_scan:    (b, s, H, P, N)
      fps:             (B, n_points, n_samples)
      ball_query:      (B, n_points, n_centers, k)
      group_aggregate: (B, n_points, n_centers, k, channels)

    New domains document their conventions on their ``IsaxSpec`` entries.
    """

    op: str
    shape: tuple[int, ...]
    dtype: str
    backend: str

    def __post_init__(self):
        if not self.op or not isinstance(self.op, str):
            raise ValueError(f"OpKey.op must be a non-empty string, "
                             f"got {self.op!r}")


def trace_kind(op: str) -> str:
    """Trace kind an op's e-graph outcome is memoized under (attention
    prefill/decode/paged all share the ``attention`` saturation run).

    Registry-backed: the *engine* memoizes on the spec object itself (two
    domains can never alias a kind string); this helper only reports the
    human-readable label."""
    return default_registry().op_spec(op).trace_kind


def trace_term(kind: str) -> Term:
    """The software-side program for one trace kind, resolved through the
    registry (terms are shape-independent)."""
    return default_registry().spec_for_kind(kind).trace_program()

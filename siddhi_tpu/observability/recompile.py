"""JIT trace/recompile accounting.

Reference (what): not applicable — the reference's per-event processors are
plain Java; object identity is stable and nothing ever "recompiles"
mid-stream.  TPU design (how): every query step is a `jax.jit` program
keyed on the abstract shapes/dtypes of its arguments.  A batch arriving in
a new bucket size, a weak-type leak, or an emission-cap regrow silently
re-traces and re-compiles — a sub-second stall on CPU, seconds to tens of
seconds on the TPU (steputil.py documents the observed round-4 incident:
p99 of 2150ms vs p50 14.9ms from exactly two such recompiles).  This registry makes those events *visible*: `steputil.
jit_step` calls `record(owner, args)` from inside the wrapped function —
which Python only executes while jax is TRACING a new signature — so the
count per owner is exactly the number of compiles, and the signature string
captures the triggering abstract shapes.

The registry is process-global (planners don't know their app), keyed by
owner label; `StatisticsManager.report()` projects the slice relevant to
its app.  Recording is two dict ops per COMPILE — never on the steady-state
hot path, by construction.
"""
from __future__ import annotations

import threading
import time
from collections import deque
from typing import Dict, List, Optional

_MAX_SIGNATURES = 4     # last-N triggering signatures kept per owner
_MAX_SIG_CHARS = 240


def _describe(x) -> str:
    aval = getattr(x, "aval", None)
    if aval is not None and hasattr(aval, "shape"):
        d = getattr(aval, "dtype", None)
        w = "w" if getattr(aval, "weak_type", False) else ""
        return f"{getattr(d, 'name', d)}{w}{list(aval.shape)}"
    return type(x).__name__


def signature_of(args) -> str:
    """Compact one-line abstract-shape signature of a traced call's args."""
    try:
        import jax
        leaves = jax.tree_util.tree_leaves(args)
    except Exception:  # noqa: BLE001 — accounting must never break a trace
        leaves = []
    s = " ".join(_describe(v) for v in leaves)
    if len(s) > _MAX_SIG_CHARS:
        s = s[:_MAX_SIG_CHARS] + "..."
    return s


_suppress_tls = threading.local()


class RecompileRegistry:
    def __init__(self):
        self._lock = threading.Lock()
        self._counts: Dict[str, int] = {}
        self._sigs: Dict[str, deque] = {}
        self._last_ms: Dict[str, int] = {}

    @staticmethod
    def suppressed() -> bool:
        """True while this thread is inside a diagnostic re-trace (EXPLAIN
        lowering a step for cost analysis) — those traces are not real
        recompiles and must not inflate the per-owner counters."""
        return getattr(_suppress_tls, "on", False)

    @staticmethod
    def suppress():
        """Context manager marking this thread's traces as diagnostic."""
        import contextlib

        @contextlib.contextmanager
        def _cm():
            prev = getattr(_suppress_tls, "on", False)
            _suppress_tls.on = True
            try:
                yield
            finally:
                _suppress_tls.on = prev
        return _cm()

    def record(self, owner: str, args) -> None:
        if getattr(_suppress_tls, "on", False):
            return
        sig = signature_of(args)
        with self._lock:
            self._counts[owner] = self._counts.get(owner, 0) + 1
            dq = self._sigs.get(owner)
            if dq is None:
                dq = self._sigs[owner] = deque(maxlen=_MAX_SIGNATURES)
            dq.append(sig)
            self._last_ms[owner] = int(time.time() * 1000)

    def count(self, owner: str) -> int:
        return self._counts.get(owner, 0)

    def snapshot(self, owners: Optional[List[str]] = None) -> Dict:
        """{owner: {count, last_ms, signatures}} — all owners, or just the
        requested ones (an app projecting its own queries)."""
        with self._lock:
            keys = list(self._counts) if owners is None else \
                [o for o in owners if o in self._counts]
            return {o: {"count": self._counts[o],
                        "last_ms": self._last_ms.get(o, 0),
                        "signatures": list(self._sigs.get(o, ()))}
                    for o in keys}

    def owners_with_prefix(self, prefix: str) -> List[str]:
        with self._lock:
            return [o for o in self._counts if o.startswith(prefix)]

    def reset(self) -> None:
        with self._lock:
            self._counts.clear()
            self._sigs.clear()
            self._last_ms.clear()


RECOMPILES = RecompileRegistry()

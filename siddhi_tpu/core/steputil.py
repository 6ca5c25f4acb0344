"""Step-function hygiene shared by every runtime's jitted step.

Reference behavior (what): the reference's per-event processors are plain
Java — object identity is stable, so a processor never "recompiles"
mid-stream (JoinProcessor.java, StreamPreStateProcessor.java run the same
bytecode for every event).

TPU design (how): our steps are jit-compiled `(state, batch) -> (state',
out)` programs, so the analogous guarantee is *compile-signature
stability*: the state a step RETURNS must have exactly the avals of the
state it ACCEPTS, or the very next call re-traces and re-compiles — a
sub-second stall on CPU and seconds to tens of seconds on the TPU (PERF.md
"PR 21" lists the per-program compile times).  The one way a shape-stable
pytree drifts is jax weak typing:
an arithmetic mix of a Python scalar and an array yields `weak_type=True`
leaves, while host-staged init state is strong-typed, so the first timed
batch after warmup recompiles every step (observed: the round-4
windowed_join p99 of 2150ms vs p50 14.9ms was exactly two such
recompiles).  `strongify` canonicalizes every returned leaf to its strong
dtype (a no-op in XLA for already-strong leaves); `jit_step` wraps a step
so all outputs are canonicalized before they leave the jit boundary.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax


def pmin_i64(x, axis: str):
    """Cross-shard minimum of an s64 scalar inside a shard_map, built from
    a SUM all-reduce.  XLA:TPU emulates s64 as two u32 halves and lowers
    no other reduction for it — `lax.pmin` on the i64 wake scalar fails at
    run time on a real mesh with "UNIMPLEMENTED: Supported lowering only
    of Sum all reduce" (the virtual CPU mesh accepts it).  Each device
    writes its value into its own slot of an [n] zero vector; the psum is
    then a gather (every slot has exactly one non-zero addend, so it is
    exact) and the minimum is local."""
    n = jax.lax.axis_size(axis)
    mine = jax.numpy.zeros((n,), x.dtype).at[
        jax.lax.axis_index(axis)].set(x)
    return jax.numpy.min(jax.lax.psum(mine, axis))


def _strong_leaf(x):
    if isinstance(x, (bool, int, float, complex)):
        # a literal scalar leaf would leave the jit boundary weak-typed;
        # canonicalize it to the strong default dtype for its kind
        a = jax.numpy.asarray(x)
        return jax.lax.convert_element_type(a, a.dtype)
    aval = getattr(x, "aval", None)
    weak = aval.weak_type if aval is not None else \
        getattr(x, "weak_type", False)
    if weak:
        return jax.lax.convert_element_type(x, x.dtype)
    return x


def strongify(tree):
    """Canonicalize every weak-typed array leaf to its strong dtype."""
    return jax.tree.map(_strong_leaf, tree)


def fuse_step(body, owner=None, role=None):
    """K query steps in ONE device dispatch: `body(carry, x, const) ->
    (carry', y)` becomes a jitted `fused(carry, xs, const) -> (carry',
    ys)` running `lax.scan` over the leading [K] axis of every `xs` leaf.

    This is the deep-batching lever: per-dispatch and per-fetch fixed
    costs (host dispatch, the H2D submit, the blocking emission-header
    fetch — not measured on the current chip) divide by K because K
    staged micro-batches ride one transfer, one XLA execution, and one
    emission-header fetch.  State threads through the scan carry exactly
    as it threads through K sequential `jit_step` calls; the carry is
    `strongify`-ed every iteration so a weak-typed leaf can never make
    the carry aval drift mid-scan (the same guarantee jit_step gives at
    the jit boundary).

    `owner` should be the fused recompile owner (`fused:<query>`) so a
    K-change or shape-change recompile is attributed in /metrics instead
    of appearing as a silent re-trace of the base step."""

    def fused(carry, xs, const):
        def scan_body(c, x):
            c2, y = body(c, x, const)
            return strongify(c2), y
        return jax.lax.scan(scan_body, carry, xs)

    return jit_step(fused, owner=owner, role=role, donate_argnums=(0,))


def jit_step(fn, owner=None, role=None, **jit_kwargs):
    """`jax.jit` with compile-signature-stable outputs: every returned
    leaf is strong-typed, so feeding returned state back into the step
    can never re-trace.  Drop-in for `jax.jit(fn, donate_argnums=...)`.

    `owner` labels this step for recompile accounting: the wrapped body
    only executes while jax is TRACING a new signature, so recording there
    counts exactly the compile events — with the triggering abstract
    shapes — at zero steady-state cost (observability/recompile.py).  The
    trace runs inside a `siddhi:compile` span (observability/phases.py),
    so a recompile-stalled batch is self-evident in a profiler capture
    and in a DETAIL trace dump.

    `role` names the traced function, so the XLA module — and every
    device op of it in a profiler trace — is `jit_<role>`:
    `pattern_dense`, `plain_step`, `join_left`, ...  Roles, not query
    names: a bounded set, the same across apps, so a trace reduction
    finds a step after a refactor."""
    from ..observability.recompile import RECOMPILES
    from ..observability.phases import phase
    label = owner or getattr(fn, "__qualname__", None) or "step"
    # last-traced argument avals, captured for EXPLAIN: observability/
    # explain.py re-lowers the jitted step from these ShapeDtypeStructs to
    # run XLA cost analysis on exactly the signature that actually ran
    # (specs are tiny host objects — no arrays are retained)
    spec_holder = {"argspecs": None}

    @functools.wraps(fn)
    def wrapped(*args, **kwargs):
        if RECOMPILES.suppressed():
            # diagnostic re-trace (EXPLAIN cost analysis): no recompile
            # accounting AND no compile-gate admission — diagnostics
            # must never queue behind (or penalize) real compiles
            return strongify(fn(*args, **kwargs))
        # this body only executes while jax traces a NEW signature, so
        # the shared compile-admission gate (core/admission.py) wraps
        # exactly the compile events: traces serialize process-wide and
        # an app over its admission.max.recompiles.per.min budget pays
        # its penalty before contending — a storming tenant's compiles
        # queue behind everyone else instead of in front
        from .admission import COMPILE_GATE
        with COMPILE_GATE.admit(label):
            RECOMPILES.record(label, args)
            try:
                spec_holder["argspecs"] = jax.tree.map(
                    lambda x: jax.ShapeDtypeStruct(x.aval.shape,
                                                   x.aval.dtype), args)
            except Exception:  # noqa: BLE001 — accounting must not break
                pass           # a trace (e.g. non-array leaves)
            with phase(None, None, "compile", owner=label):
                return strongify(fn(*args, **kwargs))

    if role is not None:
        wrapped.__name__ = wrapped.__qualname__ = role
    jitted = jax.jit(wrapped, **jit_kwargs)
    try:
        jitted._siddhi_owner = label
        jitted._siddhi_role = wrapped.__name__
        jitted._siddhi_argspec = spec_holder
    except Exception:  # noqa: BLE001 — attribute support is best-effort
        pass
    return jitted


# ---------------------------------------------------------------------------
# row arrays as u32 planes: how a step moves or ships rows whole

def split64(x):
    """(low words, high words) of an int64 array, as u32.  Both halves
    are in u32's range before the convert, so it is exact on every
    backend (no reliance on a wrapping narrow)."""
    return ((x & 0xFFFFFFFF).astype(jnp.uint32),
            lax.shift_right_logical(
                x, jnp.asarray(32, jnp.int64)).astype(jnp.uint32))


def join64(lo, hi):
    """The int64 array of `split64`'s two planes."""
    return (hi.astype(jnp.int64) << 32) | lo.astype(jnp.int64)


def u32_planes(x) -> list:
    """The u32 planes of one row array (the emission wire's, and a stacked
    row gather's: `pattern_planner._gathering`, `window.gather_packed`): a
    64-bit array as (low words, high words) — XLA:TPU keeps it so anyway,
    and a `device_get` of an 8-byte dtype costs ten times a 4-byte one's
    (6.8 against 0.67 ms at 262,144 elements: PERF.md, PR 31) — a 4-byte
    one bit for bit, a bool as 0 / 1."""
    if x.dtype.itemsize == 8:      # int64: the device has no other
        return list(split64(x))
    if x.dtype.itemsize == 4:
        return [lax.bitcast_convert_type(x, jnp.uint32)]
    return [x.astype(jnp.uint32)]


def from_u32_planes(planes, dtype):
    """`u32_planes` undone on the device: the next plane(s) of the
    iterator as one array of `dtype`."""
    if np.dtype(dtype).itemsize == 8:
        return join64(next(planes), next(planes))
    if np.dtype(dtype).itemsize == 4:
        return lax.bitcast_convert_type(next(planes), dtype)
    return next(planes).astype(dtype)

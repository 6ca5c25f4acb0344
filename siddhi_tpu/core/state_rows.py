"""The row-mover of the gather-path pattern step: how a step's keys' state
rows get from the resident `[W, K]` arrays (`StatePacker`: `b32`, `lo64`,
`hi64`, key axis minor) into the `[W, Kb]` sub-arrays the scan runs over,
and back.

Two forms, picked from what the mover can observe (`block_form`):

* XLA's: `a[:, key_idx]` and `a.at[:, key_idx].set(n, mode="drop")`.  On
  the v5e both are a serial loop over the INDICES, one pass an array,
  whatever the bytes: 54-59 ns an index an array to gather, 139-148 ns to
  scatter (ledger, PR 35: 0.66 + 1.82 ms of the paced step's 2.58, 11.5 +
  27.3 ms of the mesh step's 42.6), and a pad row pays like a live one.
* by the 128-KEY LANE BLOCK, two Pallas kernels.  The arrays are tiled
  `T(8,128)` with the keys minor, so the 128 keys `[128 b, 128 b + 128)`
  of an array are `ceil(W / 8)` whole tiles: a block is a plain aligned
  DMA where a key's column is W strided words.  `key_idx` arrives sorted
  ascending with its pads (index >= K) at the tail (the layout contract of
  `keyslots.group_events_by_key`), so keys of one block are adjacent: a
  block is fetched once for every lane it owes, `NSLOT` fetches in flight,
  and the loops stop at the live count — a pad row costs nothing.  128
  consecutive keys that start a block (the mesh sweep's local rows) move
  as one DMA a block and no lane is picked.

Both forms give the same arrays bit for bit (tests/test_state_rows.py);
no dtype narrows, no row is skipped, no write is left for later.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

# Pallas, imported by the first block-form trace (`_call`): ~1 s that a
# process which never takes the form — any CPU run, a cell whose sends are
# all dense — does not pay
pl = pltpu = None

LANES = 128
SUBLANES = 8
# block fetches in flight: ~2 us of DMA latency x 819 GB/s is ~1.6 MB, 23
# blocks of the three arrays' 69.6 KB; 32 slots are 2.3 MB of VMEM
NSLOT = 32
# rows whose lanes are picked between two looks at the DMAs: no branch
# stands among their rolls and selects (8 move a row 6 % faster and take
# 0.4 s longer to trace and lower, in every process that meets the form)
CHUNK = 4
assert CHUNK <= NSLOT // 2     # a chunk's blocks are fetched before its rows
_LANE_BITS, _CHUNK_BITS = LANES.bit_length() - 1, CHUNK.bit_length() - 1
assert (1 << _LANE_BITS, 1 << _CHUNK_BITS) == (LANES, CHUNK) and \
    NSLOT & (NSLOT - 1) == 0   # powers of two: indices by shift and mask

# test hook (as pattern_planner._FORCE_SCAN): None = observe the backend;
# "blocks" = the kernels, compiled (the no-chip v5e compile guard, whose
# process sees the CPU backend); "interpret" = the kernels in Pallas'
# interpret mode (tier-1 runs them on the CPU so)
_FORM = None


def block_form(K: int, Kb: int) -> bool:
    """Blocks where the backend is a TPU, the key axis is whole lane
    blocks and the rectangle has at least one block of rows; else XLA's
    form (a rectangle under 128 rows moves in ~0.04 ms as it is)."""
    on = _FORM is not None or jax.default_backend() == "tpu"
    return on and K % LANES == 0 and Kb % LANES == 0


def live_count(key_idx, K: int):
    """Rows of `key_idx` that name a key: the pads (>= K) are its tail."""
    return jnp.sum(key_idx < K, dtype=jnp.int32)


def load(arrays, key_idx, n_live):
    """The `[W, Kb]` sub-arrays of `arrays` (each `[W, K]`) at the columns
    `key_idx`; a pad row reads column K - 1, as XLA's clamping gather
    does."""
    K, Kb = arrays[0].shape[1], key_idx.shape[0]
    if not block_form(K, Kb):
        return [a[:, key_idx] for a in arrays]
    subs = _call(_load_kernel, arrays, (), key_idx, n_live)
    live = (jnp.arange(Kb, dtype=jnp.int32) < n_live)[None, :]
    return [jnp.where(live, s, a[:, K - 1:])
            for s, a in zip(subs, arrays)]


def store(arrays, news, key_idx, n_live):
    """`arrays` with the columns `key_idx` overwritten by `news`' columns,
    in place; pad rows (out of range) are dropped."""
    K, Kb = arrays[0].shape[1], key_idx.shape[0]
    if not block_form(K, Kb):
        return [a.at[:, key_idx].set(n, mode="drop")
                for a, n in zip(arrays, news)]
    return _call(_store_kernel, arrays, news, key_idx, n_live)


def _tile_rows(W: int) -> int:
    """The rows a `[W, 128]` block holds in memory: whole (8, 128) tiles."""
    return -(-W // SUBLANES) * SUBLANES


def _call(kernel, arrays, news, key_idx, n_live):
    """One `pallas_call` over the rectangle's row blocks: grid step i
    owns rows [128 i, 128 i + 128).  The resident arrays stay where they
    are (`ANY`) and are touched by DMA alone; `news` (the store's) and the
    load's results go through VMEM a `[W, 128]` block a step."""
    global pl, pltpu
    if pl is None:
        from jax.experimental import pallas as pl
        from jax.experimental.pallas import tpu as pltpu
    n = len(arrays)
    Kb = key_idx.shape[0]
    storing = bool(news)
    interpret = _FORM == "interpret"
    any_spec = pl.BlockSpec(memory_space=pl.ANY)
    row_specs = [pl.BlockSpec((a.shape[0], LANES), lambda i, *_: (0, i))
                 for a in arrays]
    slots = [pltpu.VMEM((NSLOT, _tile_rows(a.shape[0]), LANES), a.dtype)
             for a in arrays]
    if storing:
        in_specs, out_specs = [any_spec] * n + row_specs, [any_spec] * n
        out_shape = [_like(a, a.shape) for a in arrays]
        # operands 0 and 1 are the prefetched scalars
        aliases = {2 + i: i for i in range(n)}
    else:
        in_specs, out_specs = [any_spec] * n, row_specs
        out_shape = [_like(a, (a.shape[0], Kb)) for a in arrays]
        aliases = {}
    # a DMA moves whole tiles: of an array whose W is no multiple of 8 the
    # compiled kernel moves the last tile's pad rows with it (Mosaic slices
    # no 50 rows off the 56 the array holds in HBM); they come back as
    # they went.  The interpreter's arrays have no pad rows to move
    rows = [a.shape[0] if interpret else _tile_rows(a.shape[0])
            for a in arrays]
    # the repo runs with x64 on; the kernels hold 32-bit words alone and
    # Mosaic's scalar core takes no int64 index
    with jax.enable_x64(False):
        return pl.pallas_call(
            functools.partial(kernel, rows),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=2, grid=(Kb // LANES,),
                in_specs=in_specs, out_specs=out_specs,
                scratch_shapes=slots + [
                    pltpu.SMEM((LANES,), jnp.int32),
                    pltpu.SMEM((LANES,), jnp.int32),
                    pltpu.SemaphoreType.DMA((2, n, NSLOT))]),
            out_shape=out_shape, input_output_aliases=aliases,
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",)),
            name="state_rows_store" if storing else "state_rows_load",
            interpret=interpret,
        )(key_idx.astype(jnp.int32), jnp.reshape(n_live, (1,)), *arrays,
          *news)


def _like(a, shape):
    """A result of `a`'s dtype, varying over the mesh axes `a` varies
    over (inside a `shard_map` the kernel runs on each chip's share)."""
    return jax.ShapeDtypeStruct(shape, a.dtype, vma=jax.typeof(a).vma)


class _Blocks:
    """What both kernels share: this grid step's rows, the distinct key
    blocks they name, and the DMAs between a block and its VMEM slot."""

    def __init__(self, rows, keys, n_live, arrays, slots, ublk, ordv, sem):
        self.rows, self.keys, self.arrays, self.slots = \
            rows, keys, arrays, slots
        self.ublk, self.ordv, self.sem = ublk, ordv, sem
        self.base = pl.program_id(0) * LANES
        # live rows of this step's 128
        self.cnt = jnp.clip(n_live[0] - self.base, 0, LANES)
        k0 = keys[self.base]
        # 128 consecutive keys that start a block
        self.run = (self.cnt == LANES) & (_lane(k0) == 0) & \
            (keys[self.base + LANES - 1] - k0 == LANES - 1)
        # a zero the tracer cannot see through (`rows`, _call)
        self.zero = pl.multiple_of(n_live[0] * 0, SUBLANES)

    def key(self, r):
        return self.keys[self.base + r]

    def distinct(self):
        """The distinct blocks of the live rows into `ublk`, in order
        (sorted keys: a block's rows are adjacent), each row's block's
        place in that order into `ordv`; how many blocks."""
        def pre(r, carry):
            nd, prev = carry
            b = _block_of(self.key(r))
            new = (r == 0) | (b != prev)

            @pl.when(new)
            def _():
                self.ublk[nd] = b
            nd = nd + new.astype(jnp.int32)
            self.ordv[r] = nd - 1
            return nd, b
        return lax.fori_loop(0, self.cnt, pre, (0, 0))[0]

    def starts(self, r):
        """Live row r opens a block: the first row, or another block
        than row r - 1's."""
        return (r == 0) | (self.ordv[r] != self.ordv[jnp.maximum(r - 1, 0)])

    def copies(self, b, slot, back=False):
        """The DMAs of block b into `slot`, or `back` from it (each way
        its own semaphores: a slot's write can be in flight while the
        next block is fetched into another)."""
        out = []
        lanes = pl.ds(pl.multiple_of(b * LANES, LANES), LANES)
        for i, (a, s, w) in enumerate(zip(self.arrays, self.slots,
                                          self.rows)):
            hbm = a.at[pl.ds(self.zero, w), lanes]
            vmem = s.at[slot, pl.ds(0, w)]
            src, dst = (vmem, hbm) if back else (hbm, vmem)
            out.append(pltpu.make_async_copy(src, dst,
                                             self.sem.at[int(back), i, slot]))
        return out

    def fetch(self, d):
        """The DMAs of the d-th distinct block into its slot."""
        return self.copies(self.ublk[d], _slot(d))

    def put(self, d):
        """The DMAs of the d-th distinct block back from its slot."""
        return self.copies(self.ublk[d], _slot(d), back=True)

    def chunks(self, body, carry):
        """`body(first row, carry)` over the live rows, CHUNK at a time."""
        return lax.fori_loop(
            0, (self.cnt + CHUNK - 1) >> _CHUNK_BITS,
            lambda c, carry: body(c * CHUNK, carry), carry)

    def arrive(self, r0):
        """Wait for the blocks the chunk's rows open."""
        def row(r):
            @pl.when(self.starts(r))
            def _():
                _wait(self.fetch(self.ordv[r]))
        _loop(r0, jnp.minimum(r0 + CHUNK, self.cnt), row)

    def lanes_of(self, r0, into_row: bool):
        """[(the slot of the row's block, the lane roll that brings the
        row's lane of the block onto its lane of the `[W, 128]` rows —
        or back, `into_row` False —, the lane so filled)] of the chunk's
        rows; a row past the live ones fills lane -1: none."""
        out = []
        for i in range(CHUNK):
            r = r0 + i
            at = jnp.minimum(r, self.cnt - 1)
            k = _lane(self.key(at))
            src, dst = (k, r) if into_row else (r, k)
            out.append((_slot(self.ordv[at]), _lane(dst - src),
                        jnp.where(r < self.cnt, dst, -1)))
        return out


# the kernels' index arithmetic, by shift and mask: `%` and `//` on traced
# ints are Python's (sign-corrected), and Mosaic lowers the correction's
# boolean compare by re-tracing a helper — ~35 ms apiece on the chip's
# host, 90 of them 3 s of every process's first step (PERF.md, PR 36)
def _lane(k):
    return k & (LANES - 1)


def _block_of(k):
    return k >> _LANE_BITS


def _slot(d):
    return d & (NSLOT - 1)


def _start(copies):
    for c in copies:
        c.start()


def _wait(copies):
    for c in copies:
        c.wait()


def _loop(lo, hi, body):
    """`body(i)` for lo <= i < hi, nothing carried."""
    def step(i, c):
        body(i)
        return c
    lax.fori_loop(lo, hi, step, 0)


def _lanes(shape):
    return lax.broadcasted_iota(jnp.int32, shape, 1)


def _load_kernel(rows, keys, n_live, *refs):
    n = len(rows)
    arrays, outs, slots = refs[:n], refs[n:2 * n], refs[2 * n:3 * n]
    blk = _Blocks(rows, keys, n_live, arrays, slots, *refs[3 * n:])

    @pl.when(blk.run)
    def _():
        # the block IS the result
        copies = blk.copies(_block_of(blk.key(0)), 0)
        _start(copies)
        _wait(copies)
        for o, s in zip(outs, slots):
            o[...] = s[0, :o.shape[0]]

    @pl.when(jnp.logical_not(blk.run) & (blk.cnt > 0))
    def _():
        nd = blk.distinct()

        def chunk(r0, fetched):
            # fetches in flight up to NSLOT blocks from the chunk's first
            # block on: the blocks before it have given their lanes
            upto = jnp.minimum(nd, blk.ordv[r0] + NSLOT)
            _loop(fetched, upto, lambda f: _start(blk.fetch(f)))
            blk.arrive(r0)
            picked = blk.lanes_of(r0, into_row=True)
            for o, s in zip(outs, slots):
                acc, lanes = o[...], _lanes(o.shape)
                for slot, roll, lane in picked:
                    # the row's lane of its block into its lane of the
                    # result
                    moved = pltpu.roll(s[slot], roll, 1)
                    acc = jnp.where(lanes == lane, moved[:acc.shape[0]],
                                    acc)
                o[...] = acc
            return jnp.maximum(fetched, upto)
        blk.chunks(chunk, 0)


def _store_kernel(rows, keys, n_live, *refs):
    n = len(rows)
    # refs[:n], the aliased inputs, are the outputs' own memory
    news, arrays, slots = refs[n:2 * n], refs[2 * n:3 * n], refs[3 * n:4 * n]
    blk = _Blocks(rows, keys, n_live, arrays, slots, *refs[4 * n:])

    @pl.when(blk.run)
    def _():
        # the new rows ARE the block (the slot's pad rows go with them)
        for nw, s in zip(news, slots):
            s[0] = jnp.zeros(s.shape[1:], s.dtype)
            s[0, :nw.shape[0]] = nw[...]
        copies = blk.copies(_block_of(blk.key(0)), 0, back=True)
        _start(copies)
        _wait(copies)

    @pl.when(jnp.logical_not(blk.run) & (blk.cnt > 0))
    def _():
        nd = blk.distinct()

        def chunk(r0, state):
            # the blocks before the chunk's first have taken their lanes:
            # write them back; fetch NSLOT / 2 blocks ahead, each into a
            # slot whose last block's write has landed (block f's slot
            # was block f - NSLOT's, put half a ring of blocks ago)
            fetched, put, landed = state
            d = blk.ordv[r0]
            _loop(put, d, lambda b: _start(blk.put(b)))
            upto = jnp.minimum(nd, d + NSLOT // 2)
            due = jnp.maximum(landed, upto - NSLOT)
            _loop(landed, due, lambda b: _wait(blk.put(b)))
            _loop(fetched, upto, lambda f: _start(blk.fetch(f)))
            blk.arrive(r0)
            picked = blk.lanes_of(r0, into_row=False)
            for nw, s in zip(news, slots):
                new, lanes = nw[...], _lanes(nw.shape)
                for slot, roll, lane in picked:
                    # the row's lane of the new rows into its lane of its
                    # block
                    moved = pltpu.roll(new, roll, 1)
                    old = s[slot, :new.shape[0]]
                    s[slot, :new.shape[0]] = jnp.where(lanes == lane,
                                                       moved, old)
            return (jnp.maximum(fetched, upto), jnp.maximum(put, d), due)
        _, put, landed = blk.chunks(chunk, (0, 0, 0))
        # nothing is in flight when the step ends: the next step may owe
        # lanes to this one's last block
        _loop(put, nd, lambda b: _start(blk.put(b)))
        _loop(landed, nd, lambda b: _wait(blk.put(b)))

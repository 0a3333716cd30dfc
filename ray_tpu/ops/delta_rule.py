"""The gated delta rule's decode step over the state pool as a Pallas TPU
kernel (``kda_step``; models/kimi_linear.py has the mathematics).

Why: a decode step's recurrence reads each running row's state (a ``d_k x
d_v`` float32 matrix a head), decays and corrects it, and writes it back:
2 FLOPs a byte, so the chip's memory sets its time, and the least is one
read and one write of the states.  No ``jnp`` form reaches it: gathered by
row, the states moved seven times their bytes in loops over the rows;
worked by slot over a layer's whole slab, XLA makes three passes over the
pool's layer where the mathematics needs two, and moves the slots no row
names as well (PR 41, PR 47).  This kernel moves each running row's state
once in and once out, where it lies, and nothing else.

Layout: the pool stays ``[L, slots, H, d_k, d_v]`` in HBM, aliased in and
out, and is addressed at ``[layer, slots[b], h0:h1]`` by the kernel's own
copies: a block of ``block_heads`` heads (16: 1 MB at 128 x 128) is one
async copy HBM -> VMEM and one back, the read of the next block and the
write of the one before in flight beside the arithmetic of this one (two
buffers each way, 4 MB of VMEM).  A row whose slot lies outside the pool
(a padded row) starts no copy and waits for none, so a slot no row names
is neither read nor written; a fresh row's state is not read either
(zeros).  The copies set the time, not the arithmetic: with the
arithmetic taken out, the cell's 20 layers took 2.15 ms of the whole
kernel's 2.22 (blocks of 16; 2.32 in blocks of 8, 2.69 of 4, 2.23 of 32;
my chip runs, PR 47): 1.34 GB at ~620 GB/s, what this chip's memory gives
a read and a write stream together.

The arithmetic is the VPU's, in float32 (no matmul rounds the state).  A
state has ``d_k`` in the sublanes and ``d_v`` in the lanes, so the vectors
that scale its ROWS (the decay ``a``, ``k``, ``beta k`` and ``q``) are
wanted as columns: the caller's side transposes them, once a step, into
``cols [B, d_k, 4 H]`` (1 MB beside 67 MB of states at the cell's sizes),
and the kernel takes lane ``c H + h`` of it for head ``h``.  ``S^T k`` and
``S^T q`` are sums down the sublanes.

Grid (B,): one step a row, walked in order by one core (the copies of a
row's first block start in the step before).

A state whose ``d_v`` is no whole number of 128-lane tiles (Gated
DeltaNet's 96 x 192, ``models/olmo_hybrid.py``) would be padded to the next
tile in HBM and in VMEM alike (192 -> 256: a third more bytes through the
one thing that sets the time).  The pool then holds ``pack`` heads' states
SIDE BY SIDE on the lanes (``state_shape``: two heads of 192 are 384 lanes,
three whole tiles; ``d_k`` = 96 is twelve whole sublane tiles), as
``[L, slots, H / pack, d_k, pack * d_v]``: no padding anywhere.  ``v`` and
``o`` in that layout are plain reshapes of ``[B, H, d_v]``; the columns
that scale a state's rows differ between the heads of a pack, so the kernel
picks each lane's head with a select (VPU work under the copies).  The
layout is read from the shapes alone (``pool.shape[2]`` against ``q``'s
heads); ``pack`` = 1 is the layout above, program for program.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# What one copy moves at most: 16 states of 128 x 128 float32 (blocks of
# 0.5 MB and of 2 MB were no faster, the module docstring has the runs).
BLOCK_BYTES = 1 << 20


def state_shape(heads: int, d_k: int, d_v: int):
    """One sequence's states of one layer as the pool holds them, ``(heads
    / pack, d_k, pack * d_v)``: ``pack`` the fewest heads (1, 2 or 4, a
    divisor of ``heads``) whose values side by side fill whole 128-lane
    tiles; 1 where none does (such a pool stays off the kernel)."""
    pack = next((p for p in (1, 2, 4)
                 if heads % p == 0 and (p * d_v) % 128 == 0), 1)
    return heads // pack, d_k, pack * d_v


def pack_states(s, pool_heads: int):
    """[..., H, d_k, d_v] -> the pool's [..., H / pack, d_k, pack * d_v]
    (nothing at ``pack`` = 1)."""
    *lead, h, dk, dv = s.shape
    if h == pool_heads:
        return s
    pack = h // pool_heads
    return jnp.moveaxis(s.reshape(*lead, pool_heads, pack, dk, dv), -3,
                        -2).reshape(*lead, pool_heads, dk, pack * dv)


def unpack_states(s, heads: int):
    """``pack_states``' inverse: the pool's layout -> [..., H, d_k, d_v]."""
    *lead, hp, dk, w = s.shape
    if hp == heads:
        return s
    pack = heads // hp
    return jnp.moveaxis(s.reshape(*lead, hp, dk, pack, w // pack), -2,
                        -3).reshape(*lead, heads, dk, w // pack)


def supported(pool, q) -> bool:
    """Whether the compiled kernel takes these shapes: a float32 pool
    ``[L, slots, H / pack, d_k, pack * d_v]`` whose states are whole tiles
    (``d_k`` whole sublanes of 8, the lanes whole 128s) and whose heads
    ``q``'s [B, H, d_k] are a whole number of packs of."""
    return (pool.ndim == 5 and pool.dtype == jnp.float32 and q.ndim == 3
            and pool.shape[3] % 8 == 0 and pool.shape[4] % 128 == 0
            and q.shape[1] % pool.shape[2] == 0)


def _back(j, n, n_blk):
    """The block ``n`` before block ``j`` of a row (``n`` < 0: after): (how
    many rows back it lies, its number in that row), both static."""
    rows, j = divmod(j - n, n_blk)
    return -rows, j


def _step_kernel(layer_ref, slots_ref, fresh_ref, cols_ref, v_ref, s_in,
                 o_ref, s_out, in_buf, out_buf, in_sem, out_sem,
                 *, heads, hb):
    b, rows = pl.program_id(0), pl.num_programs(0)
    layer, n_slots = layer_ref[0], s_in.shape[1]
    pool_heads, width = s_in.shape[2], s_in.shape[4]
    n_blk, pack = pool_heads // hb, heads // pool_heads
    # which head of its pack a lane belongs to
    lane_head = None if pack == 1 else jax.lax.broadcasted_iota(
        jnp.int32, (1, width), 1) // (width // pack)

    def columns(h0):
        """The four vectors that scale the rows of pool head ``h0``'s
        states, each [d_k, 1], or with ``pack`` heads side by side [d_k,
        pack * d_v]: every lane its own head's."""
        def column(c, h):
            return cols_ref[0, :, c * heads + h:c * heads + h + 1]

        out = []
        for c in range(4):
            x = column(c, h0 * pack)
            for u in range(1, pack):
                x = jnp.where(lane_head >= u, column(c, h0 * pack + u), x)
            out.append(x)
        return out

    def live(row):
        return (slots_ref[row] >= 0) & (slots_ref[row] < n_slots)

    def reads(row):     # a fresh row starts from zeros
        return live(row) & (fresh_ref[row] == 0)

    def buf(row, j):
        """Which of the two buffers block ``j`` of ``row`` takes: blocks
        alternate, across the rows too."""
        return j % 2 if n_blk % 2 == 0 else (row + j) % 2

    def read(row, j):
        return pltpu.make_async_copy(
            s_in.at[layer, slots_ref[row], pl.ds(j * hb, hb)],
            in_buf.at[buf(row, j)], in_sem.at[buf(row, j)])

    def write(row, j):
        return pltpu.make_async_copy(
            out_buf.at[buf(row, j)],
            s_out.at[layer, slots_ref[row], pl.ds(j * hb, hb)],
            out_sem.at[buf(row, j)])

    def when_row(back, cond, act):
        """``act(row)`` for the row ``back`` before this one (< 0: after),
        if it is in the batch and ``cond(row)``."""
        row = jnp.clip(b - back, 0, rows - 1)

        @pl.when((b - back >= 0) & (b - back < rows) & cond(row))
        def _():
            act(row)

    @pl.when(b == 0)
    def _first():
        when_row(0, reads, lambda row: read(row, 0).start())

    for j in range(n_blk):
        # the next block's read, beside this block's arithmetic
        back, j_next = _back(j, -1, n_blk)
        when_row(back, reads, lambda row, j=j_next: read(row, j).start())
        # this block's out buffer was written from two blocks ago
        back, j_then = _back(j, 2, n_blk)
        when_row(back, live, lambda row, j=j_then: write(row, j).wait())

        @pl.when(live(b))
        def _block(j=j):
            slot = buf(b, j)

            @pl.when(fresh_ref[b] == 0)
            def _():
                read(b, j).wait()

            @pl.when(fresh_ref[b] != 0)
            def _():
                in_buf[slot] = jnp.zeros(in_buf.shape[1:], in_buf.dtype)

            for i in range(hb):
                h = j * hb + i
                a, k, q, kb = columns(h)
                s = a * in_buf[slot, i]                        # S~
                err = v_ref[0, h:h + 1, :] - jnp.sum(
                    s * k, axis=0, keepdims=True)              # v - S~^T k
                s = s + kb * err
                out_buf[slot, i] = s
                o_ref[0, h:h + 1, :] = jnp.sum(s * q, axis=0, keepdims=True)
            write(b, j).start()

    @pl.when(jnp.logical_not(live(b)))
    def _padded():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(b == rows - 1)
    def _drain():
        for n in (1, 0):
            back, j_then = _back(n_blk - 1, n, n_blk)
            when_row(back, live, lambda row, j=j_then: write(row, j).wait())


def kda_step(pool, layer, slots, fresh, q, k, v, a, beta,
             *, block_heads: int | None = None,
             interpret: bool | None = None):
    """The recurrence once for every row of a decode batch, each on its
    slot of layer ``layer`` of the WHOLE pool ``[L, slots, H / pack, d_k,
    pack * d_v]`` (float32; ``state_shape``): q, k ``[B, H, d_k]``, v ``[B,
    H, d_v]``, a ``[B, H, d_k]`` (a decay a key channel) or ``[B, H, 1]``
    (one a head) and beta ``[B, H]`` float32,
    ``slots`` ``[B]`` (an index outside the pool: a padded row, which
    touches nothing and gives zeros), ``fresh`` ``[B]`` (the row starts
    from zeros whatever its slot held).  ``S~ = diag(a) S``; ``S' = S~ +
    (beta k) (v - S~^T k)^T``; ``o = S'^T q``.  Live rows have distinct
    slots.  Returns (o ``[B, H, d_v]``, the pool, updated in place where
    the caller donates it); every slot no row names and every other layer
    is left as it was.  ``models/kimi_linear.py kda_step``'s by-slot form is
    its plain definition.

    ``block_heads``: the pool heads one copy moves (None: the most that
    divide the pool's heads within ``BLOCK_BYTES``).  ``interpret=None``
    runs the compiled kernel on the ``tpu`` backend and the interpreter
    elsewhere (tests)."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    f32 = jnp.float32
    if block_heads is None:
        block_heads = max(BLOCK_BYTES // (4 * pool.shape[3] * pool.shape[4]),
                          1)
    # The layer is an operand: 20 call sites cost the tracing of one.
    return _kda_step(
        pool, jnp.asarray(layer, jnp.int32).reshape(1),
        slots.astype(jnp.int32), fresh.astype(jnp.int32), q.astype(f32),
        k.astype(f32), v.astype(f32), a.astype(f32), beta.astype(f32),
        block_heads=block_heads, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("block_heads", "interpret"))
def _kda_step(pool, layer, slots, fresh, q, k, v, a, beta, *, block_heads,
              interpret):
    b, heads, dk = q.shape
    h, dv = pool.shape[2], pool.shape[4]    # heads and lanes as the pool's
    hb = max(n for n in range(1, min(block_heads, h) + 1) if h % n == 0)
    v = v.reshape(b, h, dv)
    # The four vectors that scale a state's rows, as its columns.
    cols = jnp.stack([jnp.broadcast_to(a, k.shape), k, q,
                      beta[..., None] * k], axis=1)            # [B,4,H,D]
    cols = jnp.moveaxis(cols, 3, 1).reshape(b, dk, 4 * heads)
    kernel = functools.partial(_step_kernel, heads=heads, hb=hb)
    o, pool = pl.pallas_call(
        kernel,
        name="kda_step",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(b,),
            in_specs=[
                pl.BlockSpec((1, dk, 4 * heads), lambda i, *_: (i, 0, 0)),
                pl.BlockSpec((1, h, dv), lambda i, *_: (i, 0, 0)),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=[
                pl.BlockSpec((1, h, dv), lambda i, *_: (i, 0, 0)),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            scratch_shapes=[
                pltpu.VMEM((2, hb, dk, dv), pool.dtype),
                pltpu.VMEM((2, hb, dk, dv), pool.dtype),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SemaphoreType.DMA((2,)),
            ]),
        out_shape=[jax.ShapeDtypeStruct((b, h, dv), jnp.float32),
                   jax.ShapeDtypeStruct(pool.shape, pool.dtype)],
        # the pool is operand 5, after the three prefetched scalars
        input_output_aliases={5: 1},
        # One core walks the rows in order: a row's first read starts in
        # the row before.
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(layer, slots, fresh, cols, v, pool)
    return o.reshape(b, heads, -1), pool

"""Mamba-2 SSD decode-state kernel: one layer of one decode step, in place.

A decode step needs each slot's f32 state ``(H, P, N)`` once: decay it,
add the token's input, write it back and read it out against C::

    s_new = dA * s + (x * dt) ⊗ B        y = s_new · C + D * x

The stacked state pool ``(L, R, H, P, N)`` stays in HBM, aliased input to
output.  Grid step ``b`` serves slot ``b``: its row ``rows[b]`` of layer
``layer`` (both scalar-prefetched) is DMA'd into one of ``NBUF`` VMEM
buffers, updated there head by head, and DMA'd back to the same place,
while the reads of the next ``AHEAD`` slots are in flight.  So the pool is
read once and written once, and no other copy of it exists.  On a v5e
this runs at the DMA rate of the rows alone, which is within 3% of XLA's
own elementwise read-and-write stream over the pool.

Sentinel row ids (``>= R``, the idle slots of ``serve()``) start no DMA:
nothing is read from or written to the pool for them, and their state
reads as zeros (a fresh row), so no live row is clipped onto.

TPU layout: the state's ``(P, N)`` planes tile as (8, 128) exactly at
Mamba-2 widths.  Per-head scalars (``dA``, ``dt``, ``D``) ride as one
``(1, H)`` lane row per slot, and ``x`` and ``y`` as ``(P, H)`` planes
(heads in lanes), so the body walks the heads with static lane slices and
every per-head operand broadcasts without a relayout.  The read-out
``s_new · C`` contracts the state's lane dim, which the MXU does against
C spread over 128 columns (one ``(N, 128)`` operand per group, held for
the whole slot) for a fraction of the cross-lane reductions it would take
on the vector unit; every column of the product is the read-out, and head
``h`` keeps column ``h``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

AHEAD = 2       # rows whose reads are in flight while one row computes
NBUF = 4        # VMEM state buffers: AHEAD reading, one computing, writing
LANES = 128     # columns of the read-out's C operand, one per head at most


def _ssd_decode_kernel(rows_ref, layer_ref, da_ref, dt_ref, x_ref, b_ref,
                       c_ref, d_ref, pool_ref, y_ref, pool_out_ref, buf,
                       rsem, wsem, *, B: int, R: int, H: int, G: int):
    """Grid step ``b``: slot ``b``'s state row through VMEM buffer
    ``b % NBUF``.  ``c_ref`` (G, N, 128) holds each group's C spread over
    128 columns."""
    b = pl.program_id(0)
    layer = layer_ref[0]

    def live(j):
        return rows_ref[j] < R

    def read(j):
        row = jnp.minimum(rows_ref[j], R - 1)
        return pltpu.make_async_copy(pool_ref.at[layer, row],
                                     buf.at[j % NBUF], rsem.at[j % NBUF])

    def write(j):
        row = jnp.minimum(rows_ref[j], R - 1)
        return pltpu.make_async_copy(buf.at[j % NBUF],
                                     pool_out_ref.at[layer, row],
                                     wsem.at[j % NBUF])

    @pl.when(b == 0)
    def _first():
        for j in range(min(AHEAD, B)):
            @pl.when(live(j))
            def _():
                read(j).start()

    nxt = b + AHEAD

    @pl.when(nxt < B)
    def _prefetch():
        # row nxt reuses the buffer of row nxt - NBUF: its write-back must
        # land before the read overwrites it
        prev = jnp.maximum(nxt - NBUF, 0)

        @pl.when((nxt >= NBUF) & live(prev))
        def _():
            write(prev).wait()

        @pl.when(live(nxt))
        def _():
            read(nxt).start()

    @pl.when(live(b))
    def _():
        read(b).wait()

    valid = live(b)
    slot = b % NBUF
    da, dt, dv = da_ref[...], dt_ref[...], d_ref[...]          # (1, H)
    xv, bv = x_ref[...], b_ref[...]                            # (P,H) (G,N)
    rep = H // G
    for h in range(H):
        g = h // rep
        s = jnp.where(valid, buf[slot, h], 0.0)                # (P, N)
        xh = xv[:, h:h + 1]                                    # (P, 1)
        s_new = (s * da[:, h:h + 1]
                 + (xh * dt[:, h:h + 1]) * bv[g:g + 1, :])
        buf[slot, h] = s_new
        sc = jnp.dot(s_new, c_ref[g], preferred_element_type=jnp.float32,
                     precision=jax.lax.Precision.HIGHEST)      # (P, 128)
        y_ref[:, h:h + 1] = sc[:, h:h + 1] + xh * dv[:, h:h + 1]

    @pl.when(valid)
    def _():
        write(b).start()

    @pl.when(b == B - 1)
    def _drain():
        for j in range(max(B - NBUF, 0), B):
            @pl.when(live(j))
            def _():
                write(j).wait()


def ssd_state_update_pallas(pool: jax.Array, rows: jax.Array,
                            layer: jax.Array, dA: jax.Array, dt: jax.Array,
                            x: jax.Array, Bm: jax.Array, Cm: jax.Array,
                            D: jax.Array, *, interpret: bool = True
                            ) -> tuple[jax.Array, jax.Array]:
    """One decode step of one SSD layer, for every slot, in the pool.

    pool (L, R, H, P, N) f32, updated in place (aliased to the second
    output); rows (B,) int32 pool rows, ``>= R`` for idle slots; layer ()
    int32; dA, dt (B, H) f32; x (B, H, P) f32; Bm, Cm (B, G, N) f32; D (H,)
    f32.  Returns (y (B, H, P) f32, pool)."""
    L, R, H, P, N = pool.shape
    B, G = rows.shape[0], Bm.shape[1]
    if H > LANES:
        raise ValueError(f"{H} heads do not fit the read-out's {LANES} "
                         "columns")
    f32 = jnp.float32
    kern = functools.partial(_ssd_decode_kernel, B=B, R=R, H=H, G=G)

    def per_slot(b, *_):
        return (b, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,            # rows, layer
        grid=(B,),
        in_specs=[
            pl.BlockSpec((None, 1, H), per_slot),                 # dA
            pl.BlockSpec((None, 1, H), per_slot),                 # dt
            pl.BlockSpec((None, P, H), per_slot),                 # x
            pl.BlockSpec((None, G, N), per_slot),                 # B
            pl.BlockSpec((None, G, N, LANES), lambda b, *_: (b, 0, 0, 0)),
            pl.BlockSpec((1, H), lambda b, *_: (0, 0)),           # D
            pl.BlockSpec(memory_space=pl.ANY),                    # pool
        ],
        out_specs=[pl.BlockSpec((None, P, H), per_slot),          # y
                   pl.BlockSpec(memory_space=pl.ANY)],            # pool
        scratch_shapes=[pltpu.VMEM((NBUF, H, P, N), f32),
                        pltpu.SemaphoreType.DMA((NBUF,)),
                        pltpu.SemaphoreType.DMA((NBUF,))],
    )
    state_bytes = B * H * P * N * 4
    y, pool = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=(jax.ShapeDtypeStruct((B, P, H), f32),
                   jax.ShapeDtypeStruct(pool.shape, f32)),
        input_output_aliases={8: 1},      # 2 scalar operands + 6 inputs
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        cost_estimate=pl.CostEstimate(flops=5 * B * H * P * N,
                                      bytes_accessed=2 * state_bytes,
                                      transcendentals=0),
        interpret=interpret,
        name="ssd_decode_state",
    )(rows.astype(jnp.int32), jnp.reshape(layer, (1,)).astype(jnp.int32),
      dA.reshape(B, 1, H), dt.reshape(B, 1, H), x.transpose(0, 2, 1),
      Bm, jnp.broadcast_to(Cm[..., None], (B, G, N, LANES)),
      D.reshape(1, H), pool)
    return y.transpose(0, 2, 1), pool

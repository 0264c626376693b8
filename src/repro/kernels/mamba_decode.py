"""Pallas TPU kernel: the Mamba-2 decode state update, in place.

One decode token per slot, one layer of a slot-stacked recurrent state:

    s <- exp(dt * A) * s + (dt * x) (x) B        (per head: (P, N))
    y  = s . C + D * x

The state lives in the serving engine's slot cache as float32 rows of
``T`` lanes: ``(slots, layers, R, N, T)`` with ``T = min(128, H * P)``
and ``R = H * P // T``, so row ``r`` holds the ``T // P`` heads whose
``P`` channels fill its lanes (``(H, P, N)`` transposed per row to
``(N, T)``).  In that layout every term is a broadcast along sublanes or
lanes: ``x``, ``dt``, ``A`` and ``D`` are ``(R, T)`` rows (``dt``, ``A``
and ``D`` repeated over each head's ``P`` lanes), ``B`` and ``C`` are
``(N, T)`` columns broadcast over the lanes (one group, so every head
reads the same ``B`` and ``C``), and ``y`` is a reduction over sublanes
that lands in a ``(1, T)`` row, the layout ``x`` came in.

Grid ``(slots, R // RB)``: each step moves one slot's ``RB`` rows of the
layer named by the scalar-prefetched ``layer`` in and out of VMEM.  The
state operand is aliased to the first output, so only the blocks the
grid visits are written and the other layers' rows stay where they are:
the decode program donates the slot cache and the update needs no second
copy of the state.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

KERNEL_NAME = "mamba_decode_step"
LANES = 128
BLOCK_BYTES = 1 << 20              # state bytes per grid step


def rows_per_step(rows: int, n: int, t: int) -> int:
    """``RB``: the most rows of ``(N, T)`` float32 under ``BLOCK_BYTES``
    that divide ``rows`` and fill whole sublane tiles (or all rows)."""
    fits = [rb for rb in range(1, rows + 1)
            if rows % rb == 0 and rb * n * t * 4 <= BLOCK_BYTES
            and (rb % 8 == 0 or rb == rows)]
    return max(fits, default=rows)


def _kernel(layer_ref, x_ref, dt_ref, a_ref, d_ref, b_ref, c_ref, s_ref,
            s_out, y_ref, *, rows: int):
    del layer_ref                                   # used by the index maps
    b = b_ref[0]                                    # (N, T)
    c = c_ref[0]
    for r in range(rows):
        x = x_ref[0, r:r + 1, :].astype(jnp.float32)        # (1, T)
        dt = dt_ref[0, r:r + 1, :]
        decay = jnp.exp(dt * a_ref[r:r + 1, :])
        s = s_ref[0, 0, r] * decay + b * (dt * x)            # (N, T)
        s_out[0, 0, r] = s
        y_ref[0, r:r + 1, :] = (jnp.sum(s * c, axis=0, keepdims=True)
                                + d_ref[r:r + 1, :] * x)


def mamba_decode_step(state: jax.Array, layer: jax.Array, x: jax.Array,
                      dt: jax.Array, a: jax.Array, d: jax.Array,
                      b: jax.Array, c: jax.Array, *,
                      interpret: bool = False):
    """One layer's decode update of a slot-stacked state, in place.

    state: (slots, L, R, N, T) f32; layer: () int32, the layer to update;
    x, dt: (slots, R, T) (``dt`` after softplus, repeated per head over
    its ``P`` lanes); a, d: (R, T) f32 (``A = -exp(A_log)`` and ``D``,
    repeated the same way); b, c: (slots, N, T) f32, each slot's ``B`` and
    ``C`` broadcast over the lanes.  Returns ``(new state, y (slots, R, T)
    f32)``; the new state is the input buffer, updated in place."""
    slots, _, rows, n, t = state.shape
    rb = rows_per_step(rows, n, t)
    layer = jnp.reshape(layer.astype(jnp.int32), (1,))

    def at_slot(s, h, lyr):
        return (s, h, 0)

    def at_rows(s, h, lyr):
        return (h, 0)

    def at_bc(s, h, lyr):
        return (s, 0, 0)

    def at_state(s, h, lyr):
        return (s, lyr[0], h, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(slots, rows // rb),
        in_specs=[pl.BlockSpec((1, rb, t), at_slot),            # x
                  pl.BlockSpec((1, rb, t), at_slot),            # dt
                  pl.BlockSpec((rb, t), at_rows),               # A
                  pl.BlockSpec((rb, t), at_rows),               # D
                  pl.BlockSpec((1, n, t), at_bc),               # B
                  pl.BlockSpec((1, n, t), at_bc),               # C
                  pl.BlockSpec((1, 1, rb, n, t), at_state)],    # state
        out_specs=[pl.BlockSpec((1, 1, rb, n, t), at_state),
                   pl.BlockSpec((1, rb, t), at_slot)],
    )
    # one stable name for the kernel in compiled text and device traces
    with jax.named_scope(KERNEL_NAME):
        new_state, y = pl.pallas_call(
            functools.partial(_kernel, rows=rb),
            grid_spec=grid_spec,
            out_shape=[jax.ShapeDtypeStruct(state.shape, jnp.float32),
                       jax.ShapeDtypeStruct((slots, rows, t), jnp.float32)],
            input_output_aliases={7: 0},
            interpret=interpret,
            name=KERNEL_NAME,
        )(layer, x, dt.astype(jnp.float32), a.astype(jnp.float32),
          d.astype(jnp.float32), b.astype(jnp.float32),
          c.astype(jnp.float32), state)
    return new_state, y

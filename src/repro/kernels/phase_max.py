"""Pallas segment-max kernel for the fair-share water-filling inner loop.

The simulator's rate resolution reduces to per-phase bottleneck loads:
``out[i] = max(vals[ptr[i]:ptr[i+1]])`` over a CSR layout (see
``repro.core.fairshare.phase_worst_loads``).  The batched engine
(``engine="batched"``, docs/batched.md) concatenates every affected job of
every lane into one such call per simulated event round, which is exactly
the dense, regular shape a TPU kernel wants.

Layout: the ragged CSR is gathered into one ``(nseg_pad, K_pad)`` int32
tile — row ``i`` holds segment ``i``'s values, padded with ``INT32_MIN`` so
padding never wins a max.  Both sides pad to a power of two (at least one
block), so a whole campaign compiles a handful of shapes, and every
compiled shape is kept.  The kernel runs a 2-D grid over (segment-block,
column-block); the output block index ignores the column axis, so the
sequential grid revisits each output row-block once per column-block and
accumulates a running maximum (the standard Pallas reduction idiom: init
under ``pl.when(j == 0)``, then ``out = max(out, block_max)``).

Off the TPU the kernel runs in interpret mode (numerically identical,
slow); the platform alone picks the mode.  A kernel that fails to lower or
compile raises — nothing substitutes another backend.  All paths are
integer-exact, so dispatch can never change a schedule.

Every call records the JAX monitoring event :data:`CALL_EVENT` with its
padded ``shape``, so a listener (``jax.monitoring.register_event_listener``)
can count kernel rounds and the shapes they compiled.
"""

from __future__ import annotations

from functools import cache

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

_I32_MIN = -(2 ** 31)

# segment-block × column-block tile; multiples of the (8, 128) int32 TPU
# tile so non-divisible inputs only pad, never re-layout
_BLOCK_S = 128
_BLOCK_K = 128

#: recorded once per kernel dispatch (jax.monitoring)
CALL_EVENT = "/repro/phase_max/call"


def _row_max_kernel(x_ref, o_ref):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        o_ref[...] = jnp.full_like(o_ref, _I32_MIN)

    o_ref[...] = jnp.maximum(o_ref[...],
                             x_ref[...].max(axis=1, keepdims=True))


@cache
def _row_max_call(nseg_pad: int, k_pad: int, interpret: bool):
    """Jitted pallas_call for one padded shape.  Power-of-two padding keeps
    the set of shapes small, so every one is kept."""
    grid = (nseg_pad // _BLOCK_S, k_pad // _BLOCK_K)
    fn = pl.pallas_call(
        _row_max_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((_BLOCK_S, _BLOCK_K), lambda i, j: (i, j))],
        out_specs=pl.BlockSpec((_BLOCK_S, 1), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((nseg_pad, 1), jnp.int32),
        interpret=interpret,
        name="phase_row_max",
    )
    return jax.jit(fn)


def _bucket(n: int, block: int) -> int:
    """Smallest power of two >= ``n``, and at least ``block``."""
    return max(block, 1 << (max(n, 1) - 1).bit_length())


def _dense_tile(vals: np.ndarray, ptr: np.ndarray) -> np.ndarray:
    """CSR -> the kernel's padded ``(nseg_pad, k_pad)`` int32 row tile
    (host-side gather; the reduction is the kernel's job)."""
    width = np.diff(ptr)
    k_pad = _bucket(int(width.max()), _BLOCK_K)
    nseg_pad = _bucket(len(width), _BLOCK_S)
    col = np.arange(k_pad)
    valid = col[None, :] < width[:, None]
    idx = np.where(valid, ptr[:-1, None] + col[None, :], 0)
    dense = np.full((nseg_pad, k_pad), _I32_MIN, dtype=np.int32)
    dense[:len(width)] = np.where(valid, vals[idx], _I32_MIN)
    return dense


def phase_worst_pallas(vals: np.ndarray, ptr: np.ndarray) -> np.ndarray:
    """Pallas twin of ``fairshare.phase_worst_numpy`` (identical integer
    output, including empty segments -> 0 and negative values)."""
    nseg = len(ptr) - 1
    if not len(vals) or nseg == 0:
        return np.zeros(nseg, dtype=np.int64)
    vals = np.asarray(vals)
    ptr = np.asarray(ptr)
    dense = _dense_tile(vals, ptr)
    call = _row_max_call(*dense.shape, jax.default_backend() != "tpu")
    jax.monitoring.record_event(CALL_EVENT, shape="%dx%d" % dense.shape)
    res = np.asarray(call(dense))[:nseg, 0].astype(np.int64)
    return np.where(np.diff(ptr) > 0, res, 0)

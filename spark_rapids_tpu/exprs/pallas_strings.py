"""Pallas string kernels over the char matrix (docs/compressed.md,
"String kernel coverage").

The XLA string kernels in ``exprs/strings.py`` unroll their pattern
loop at trace time — ``Contains`` emits one shifted comparison per
pattern byte, which is ideal for short literals and pathological for
long ones (a 64-byte needle is 64 full-width comparisons in the HLO,
which XLA:TPU no longer fuses: each shifted slice lands in HBM).
This module carries the Pallas alternative: a ``fori_loop`` over the
pattern bytes inside ONE kernel, so the program size is constant in
the pattern length and the VPU walks the char matrix once.

Layout: the kernel reads the char matrix TRANSPOSED — bytes down the
sublanes, rows across the lanes — so a window shift is a sublane
rotate (``pltpu.roll``, which Mosaic lowers for a traced shift) and
every per-row result is lane-dense.  A row grid streams one
``(width, 8 * C)`` block at a time through VMEM; the matrix itself
stays in HBM.  Off-TPU the same kernel runs in interpret mode
(compile/service.py:pallas_interpret); on the chip a lowering error
propagates — nothing here degrades to the XLA path.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from spark_rapids_tpu.exprs.base import ColVal
from spark_rapids_tpu.exprs.strings import Contains

# Literals at least this long route to the Pallas kernel;
# functions.contains reads this.  It is a memory rule set by the chip's
# compiler, not a speed one (tests/test_tpu_compile.py pins it): up to 23
# bytes XLA:TPU fuses the unroll into one pass with no HBM temp whatever
# the needle's bytes; from 24 on a needle of distinct bytes has its
# shifted slices materialised — 0.3-6 GB per 2^20-row batch at widths
# 32-256, and a 128-byte needle over a 256-wide column no longer
# compiles.  On time the kernel is ahead only past that limit on wide
# matrices (14.1 vs 15.4 ms at (2^20, 128), 30 bytes) and behind at
# width 64 (PERF.md, PR 21); ROADMAP S9 owns the rest.
PALLAS_PATTERN_MIN = 24

_SUBLANES = 8     # per-row result tile: (8, C) int32
_LANES = 512      # C, rows per result sublane (128 for small inputs)
_CHAR_TILE = 32   # uint8 sublane tiling: width pads to a multiple


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def _run_contains(chars: jnp.ndarray, lengths: jnp.ndarray,
                  pat: bytes) -> jnp.ndarray:
    """``out[r]`` <- some window of ``chars[r, :lengths[r]]`` equals
    ``pat``."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from spark_rapids_tpu.compile import service

    rows, w = chars.shape
    k = len(pat)
    c = _LANES if rows >= _SUBLANES * _LANES else 128
    blk = _SUBLANES * c
    rows_p = _round_up(rows, blk)
    w_p = _round_up(w, _CHAR_TILE)
    # padded rows have length 0 and padded bytes sit past every length,
    # so neither can complete a window
    chars_t = jnp.pad(chars, ((0, rows_p - rows), (0, w_p - w))).T
    lens2 = jnp.pad(lengths.astype(jnp.int32),
                    (0, rows_p - rows)).reshape(rows_p // c, c)
    pat_arr = jnp.asarray(np.frombuffer(pat, np.uint8).astype(np.int32))
    zero = np.int32(0)

    def kernel(pat_ref, chars_ref, lens_ref, out_ref):
        pos = jax.lax.broadcasted_iota(jnp.int32, (w_p, c), 0)
        for a in range(_SUBLANES):
            x = chars_ref[:, a * c:(a + 1) * c].astype(jnp.int32)

            def step(p, acc):
                # rotate byte j+p up to sublane j; a window that wraps
                # past w_p starts beyond lengths - k and is masked below
                shifted = pltpu.roll(x, jnp.int32(w_p) - p, 0)
                return jnp.where(shifted == pat_ref[p], acc, zero)

            # int32 carry: Mosaic does not legalize an i1 loop carry.
            # Traced int32 bounds: with concrete ones the loop becomes a
            # scan whose index Mosaic's convert_element_type rule
            # recurses on without end
            acc = jax.lax.fori_loop(jnp.int32(0), jnp.int32(k), step,
                                    jnp.ones((w_p, c), jnp.int32))
            ok = (acc != 0) & (pos + np.int32(k) <= lens_ref[a:a + 1, :])
            out_ref[a:a + 1, :] = jnp.max(ok.astype(jnp.int32), axis=0,
                                          keepdims=True)

    out = pl.pallas_call(
        kernel,
        grid=(rows_p // blk,),
        in_specs=[
            pl.BlockSpec((k,), lambda i: (zero,),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((w_p, blk), lambda i: (zero, i)),
            pl.BlockSpec((_SUBLANES, c), lambda i: (i, zero)),
        ],
        out_specs=pl.BlockSpec((_SUBLANES, c), lambda i: (i, zero)),
        out_shape=jax.ShapeDtypeStruct((rows_p // c, c), jnp.int32),
        interpret=service.pallas_interpret(),
    )(pat_arr, chars_t, lens2)
    return out.reshape(rows_p)[:rows] != 0


class PallasContains(Contains):
    """``Contains`` with the pattern loop in a Pallas kernel — same
    semantics, constant program size in the pattern length."""

    def key(self) -> str:
        return "Pallas" + super().key()

    def _match(self, c: ColVal) -> jnp.ndarray:
        k = len(self.pat)
        w = c.chars.shape[1]
        if k == 0:
            return jnp.ones_like(c.validity)
        if k > w:
            return jnp.zeros_like(c.validity)
        return _run_contains(c.chars, c.data, self.pat)

"""Blocked associative-scan Pallas TPU kernel for the gated linear recurrence.

Fuses the RNN unroll ``h_t = a_t * h_{t-1} + b_t`` (elementwise over a
flattened feature dim) into one kernel.  The first-order recurrence is
associative under the affine-composition combine

    (a1, b1) (+) (a2, b2) = (a2 * a1, a2 * b1 + b2)

so each (time chunk, feature block) tile runs a *log-depth* Hillis-Steele
scan over its chunk instead of a sequential loop, then splices the chunk
onto the running carry with one multiply-add: the inclusive prefix
``(A_t, B_t)`` of a chunk maps the incoming hidden state straight to
``h_t = A_t * h_in + B_t``.  Each of the ``ceil(log2(chunk))`` steps
shifts the whole tile down the sublane axis with ``pltpu.roll`` and masks
the wrapped rows with an iota, so Mosaic never sees a zero-length or
strided slice of the tile (``lax.associative_scan`` forms those, and
Mosaic refuses them).

Grid is (feature blocks, seq chunks) with the seq dim innermost/sequential;
the carry lives in VMEM scratch and persists across chunks (the
selective_scan layout).  Episode-boundary resets arrive as a mask operand
and fold into the decay coefficient *inside* the kernel body
(``a_t <- a_t * (1 - reset_t)``): a reset row is simply a row whose decay
is zero, so no separate carry-masking pass exists at all — this is how the
memory-core protocol's ``reset_carry`` rule moves into the kernel.

block_d is chosen a multiple of 128 (lane width); chunk rides the sublane
dim, so (chunk, block_d) tiles satisfy the f32 (8, 128) minimum.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _combine(left, right):
    """Affine composition: apply ``left`` first, then ``right``."""
    a1, b1 = left
    a2, b2 = right
    return a2 * a1, a2 * b1 + b2


def _scan_kernel(
    a_ref,      # (chunk, bd)
    b_ref,      # (chunk, bd)
    r_ref,      # (chunk, bd) — reset mask, broadcast over features
    h0_ref,     # (1, bd)
    out_ref,    # (chunk, bd)
    h_ref,      # scratch (1, bd) fp32 — carry across seq chunks
    *,
    chunk: int,
):
    """One (time chunk, feature block) tile of the blocked scan."""
    ic = pl.program_id(1)

    @pl.when(ic == 0)
    def _init():
        h_ref[...] = h0_ref[...].astype(jnp.float32)

    a = a_ref[...].astype(jnp.float32)
    b = b_ref[...].astype(jnp.float32)
    # reset_carry masking, in-kernel: zero the decay where a row opens a
    # new episode, so the recurrence restarts from b_t alone
    a = a * (1.0 - r_ref[...].astype(jnp.float32))
    # log-depth inclusive prefix of the affine maps within the chunk
    # (Hillis-Steele): at step k every row composes with the partial
    # prefix k rows above it, brought down by a whole-tile sublane roll;
    # rows with nothing k above them compose with the identity map
    row = jax.lax.broadcasted_iota(jnp.int32, a.shape, 0)
    k = 1
    while k < chunk:
        has_prev = row >= k
        a_prev = jnp.where(has_prev, pltpu.roll(a, k, 0), 1.0)
        b_prev = jnp.where(has_prev, pltpu.roll(b, k, 0), 0.0)
        a, b = _combine((a_prev, b_prev), (a, b))
        k *= 2
    h = a * h_ref[...] + b          # splice onto the carried-in state
    out_ref[...] = h.astype(out_ref.dtype)
    h_ref[...] = h[chunk - 1 : chunk]


def linear_scan_kernel(
    a,
    b,
    reset,
    h0,
    *,
    block_d: int = 512,
    chunk: int = 128,
    interpret: bool = False,
):
    """a, b, reset: (T, D); h0: (1, D) -> hs (T, D).

    Caller pads T to a chunk multiple and D to a block_d multiple
    (zero rows/columns are inert: a=0, b=0 holds h at 0).
    """
    T, D = a.shape
    block_d = min(block_d, D)
    chunk = min(chunk, T)
    assert T % chunk == 0, (T, chunk)
    assert D % block_d == 0, (D, block_d)
    nd = D // block_d
    nc = T // chunk

    kernel = functools.partial(_scan_kernel, chunk=chunk)
    return pl.pallas_call(
        kernel,
        grid=(nd, nc),
        in_specs=[
            pl.BlockSpec((chunk, block_d), lambda id_, ic: (ic, id_)),
            pl.BlockSpec((chunk, block_d), lambda id_, ic: (ic, id_)),
            pl.BlockSpec((chunk, block_d), lambda id_, ic: (ic, id_)),
            pl.BlockSpec((1, block_d), lambda id_, ic: (0, id_)),
        ],
        out_specs=pl.BlockSpec((chunk, block_d), lambda id_, ic: (ic, id_)),
        out_shape=jax.ShapeDtypeStruct((T, D), a.dtype),
        scratch_shapes=[pltpu.VMEM((1, block_d), jnp.float32)],
        interpret=interpret,
    )(a, b, reset, h0)

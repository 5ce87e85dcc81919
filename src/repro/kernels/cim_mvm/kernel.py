"""Pallas TPU kernel for the bit-sliced CIM crossbar MVM.

Hardware adaptation (see DESIGN.md §3): the analog crossbar's compute
semantics — bit-serial DAC phases x cell-precision weight slices x
per-``parallel_row``-group ADC saturation x digital shift-accumulate —
map exactly onto integer MXU matmuls over bit-planes.  The tiling is
TPU-native rather than a port of the analog array:

  * grid = (M tiles, C tiles, row-block tiles); the row-block axis is the
    innermost grid dim so partial sums accumulate into the same VMEM out
    block (classic matmul revisiting pattern);
  * bit planes are laid out as leading non-tiled axes, pre-transposed by
    ops.py so the kernel body is pure batched ``dot_general`` — no
    in-kernel reshapes/transposes (TPU layouts stay trivial);
  * row groups become the batch dim of an MXU batch matmul, int8 x int8
    -> int32, or bf16 x bf16 -> f32 for 8-bit planes (ops._plane_dtype);
    the ADC clamp is a VPU ``minimum`` on the int32 sum of each read;
  * block sizes keep the lane dim at 128 and the working set in VMEM
    (see ops.py block-size policy).

Validated bit-exactly against ref.cim_mvm_ref (interpret mode on CPU;
the same pallas_call lowers for TPU targets).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _acc_dtype(planes) -> jnp.dtype:
    """int8 planes accumulate in int32 on the MXU; bf16 planes (an 8-bit
    DAC or cell, see ops._plane_dtype) in float32, exact below 2^24."""
    return jnp.int32 if planes.dtype == jnp.int8 else jnp.float32


def _kernel(xpg_ref, wsg_ref, out_ref, *, dac_bits: int, cell_bits: int,
            adc_max: int, n_phases: int, n_slices: int):
    """One (bm x bc) output block, one row-block of gb groups.

    xpg_ref: (P, gb, bm, pr)   input bit-planes, grouped rows
    wsg_ref: (S, gb, pr, bc)   weight bit-slices, grouped rows
    out_ref: (bm, bc) int32    accumulated across the row-block grid dim
    """
    k = pl.program_id(2)
    acc = jnp.zeros(out_ref.shape, jnp.int32)
    for p in range(n_phases):
        xg = xpg_ref[p]                       # (gb, bm, pr)
        for s in range(n_slices):
            wg = wsg_ref[s]                   # (gb, pr, bc)
            # analog column sum of one activation: batched over groups
            part = jax.lax.dot_general(
                xg, wg,
                dimension_numbers=(((2,), (1,)), ((0,), (0,))),
                preferred_element_type=_acc_dtype(xg))  # (gb, bm, bc)
            # ADC saturation happens per analog read (per group)
            part = jnp.minimum(part.astype(jnp.int32), adc_max)
            shift = p * dac_bits + s * cell_bits
            acc = acc + (part.sum(axis=0) << shift)

    @pl.when(k == 0)
    def _init():
        out_ref[...] = acc

    @pl.when(k > 0)
    def _accum():
        out_ref[...] = out_ref[...] + acc


def cim_mvm_pallas(xpg: jnp.ndarray, wsg: jnp.ndarray, *, dac_bits: int,
                   cell_bits: int, adc_bits: int, block_m: int,
                   block_c: int, groups_per_block: int,
                   interpret: bool = False) -> jnp.ndarray:
    """Launch the kernel.

    xpg: (P, G, M, pr)  — phases x row-groups x rows-of-x x parallel_row
    wsg: (S, G, pr, C)  — slices x row-groups x parallel_row x cols
    returns (M, C) int32.
    Shapes must already be padded to the block grid (ops.py does this).
    """
    P, G, M, pr = xpg.shape
    S, G2, pr2, C = wsg.shape
    assert (G, pr) == (G2, pr2), (xpg.shape, wsg.shape)
    assert M % block_m == 0 and C % block_c == 0 and G % groups_per_block == 0

    grid = (M // block_m, C // block_c, G // groups_per_block)
    kernel = functools.partial(
        _kernel, dac_bits=dac_bits, cell_bits=cell_bits,
        adc_max=(1 << adc_bits) - 1, n_phases=P, n_slices=S)

    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((P, groups_per_block, block_m, pr),
                         lambda i, j, k: (0, k, i, 0)),
            pl.BlockSpec((S, groups_per_block, pr, block_c),
                         lambda i, j, k: (0, k, 0, j)),
        ],
        out_specs=pl.BlockSpec((block_m, block_c), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, C), jnp.int32),
        interpret=interpret,
        name="cim_mvm",
    )(xpg, wsg)


def _tiles_kernel(xpg_ref, wsg_ref, out_ref, *, dac_bits: int,
                  cell_bits: int, adc_max: int, n_phases: int,
                  n_slices: int):
    """Same body as ``_kernel`` with a leading singleton tile axis.

    xpg_ref: (1, P, gb, bm, pr); wsg_ref: (1, S, gb, pr, bc);
    out_ref: (1, bm, bc) — accumulated across the row-block grid dim.
    """
    k = pl.program_id(3)
    acc = jnp.zeros(out_ref.shape[1:], jnp.int32)
    for p in range(n_phases):
        xg = xpg_ref[0, p]                    # (gb, bm, pr)
        for s in range(n_slices):
            wg = wsg_ref[0, s]                # (gb, pr, bc)
            part = jax.lax.dot_general(
                xg, wg,
                dimension_numbers=(((2,), (1,)), ((0,), (0,))),
                preferred_element_type=_acc_dtype(xg))  # (gb, bm, bc)
            part = jnp.minimum(part.astype(jnp.int32), adc_max)
            shift = p * dac_bits + s * cell_bits
            acc = acc + (part.sum(axis=0) << shift)

    @pl.when(k == 0)
    def _init():
        out_ref[0] = acc

    @pl.when(k > 0)
    def _accum():
        out_ref[0] = out_ref[0] + acc


def cim_mvm_tiles_pallas(xpg: jnp.ndarray, wsg: jnp.ndarray, *,
                         dac_bits: int, cell_bits: int, adc_bits: int,
                         block_m: int, block_c: int,
                         groups_per_block: int,
                         interpret: bool = False) -> jnp.ndarray:
    """Tile-batched launch: the tile axis is the *leading grid dim*.

    xpg: (T, P, G, M, pr); wsg: (T, S, G, pr, C); returns (T, M, C)
    int32.  One ``pallas_call`` covers all T crossbar tiles (instead of
    T independent launches), with the row-block axis still innermost so
    per-tile partial sums accumulate into the same out block.
    Shapes must already be padded to the block grid (ops.py does this).
    """
    T, P, G, M, pr = xpg.shape
    T2, S, G2, pr2, C = wsg.shape
    assert (T, G, pr) == (T2, G2, pr2), (xpg.shape, wsg.shape)
    assert M % block_m == 0 and C % block_c == 0 and G % groups_per_block == 0

    grid = (T, M // block_m, C // block_c, G // groups_per_block)
    kernel = functools.partial(
        _tiles_kernel, dac_bits=dac_bits, cell_bits=cell_bits,
        adc_max=(1 << adc_bits) - 1, n_phases=P, n_slices=S)

    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, P, groups_per_block, block_m, pr),
                         lambda t, i, j, k: (t, 0, k, i, 0)),
            pl.BlockSpec((1, S, groups_per_block, pr, block_c),
                         lambda t, i, j, k: (t, 0, k, 0, j)),
        ],
        out_specs=pl.BlockSpec((1, block_m, block_c),
                               lambda t, i, j, k: (t, i, j)),
        out_shape=jax.ShapeDtypeStruct((T, M, C), jnp.int32),
        interpret=interpret,
        name="cim_mvm_tiles",
    )(xpg, wsg)

"""Public wrappers around the CIM-MVM kernel, routed by the backend
registry.

``cim_mvm``       — unsigned bit-sliced crossbar MVM.
``cim_mvm_tiles`` — tile-batched MVM (the executor fast path).
``cim_mvm_signed`` — signed ints via offset encoding (the standard CIM
                     trick: store w + 2^(wb-1), subtract the rank-1
                     correction digitally).
``cim_mvm_params`` — derive the precision/row parameters from a CIMArch.

Execution routing is a :mod:`repro.kernels.backend` decision, not a
caller-threaded boolean: every entry point resolves a
:class:`~repro.kernels.backend.KernelRoute` (``compiled`` pallas_call
on TPU/GPU, the XLA-compiled oracle on CPU, the Pallas interpreter on
request) unless the caller forces ``mode=``.  The pre-registry
``use_kernel=``/``interpret=`` keyword arguments still work but are
deprecated and emit a ``DeprecationWarning``.
"""
from __future__ import annotations

import dataclasses
import functools
import math
import warnings
from typing import Optional

import jax
import jax.numpy as jnp

from .. import backend
from . import ref
from .kernel import cim_mvm_pallas, cim_mvm_tiles_pallas


@dataclasses.dataclass(frozen=True)
class CimMvmParams:
    act_bits: int = 8
    weight_bits: int = 8
    dac_bits: int = 1
    cell_bits: int = 2
    parallel_row: int = 8
    adc_bits: int = 8

    @property
    def exact(self) -> bool:
        """True if the ADC never saturates (pure integer matmul)."""
        need = ref.exact_adc_bits(self.act_bits, self.weight_bits,
                                  self.dac_bits, self.cell_bits,
                                  self.parallel_row)
        return self.adc_bits >= need


def cim_mvm_params(arch, rows_used: Optional[int] = None) -> CimMvmParams:
    """Build params from a core.abstraction.CIMArch."""
    xb = arch.xb
    pr = xb.parallel_row
    if rows_used is not None:
        pr = min(pr, rows_used)
    return CimMvmParams(act_bits=arch.act_bits, weight_bits=arch.weight_bits,
                        dac_bits=xb.dac_bits, cell_bits=xb.cell_precision,
                        parallel_row=pr, adc_bits=xb.adc_bits)


def _pad_to(x: jnp.ndarray, axis: int, mult: int) -> jnp.ndarray:
    n = x.shape[axis]
    pad = (-n) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def _block_policy(m: int, c: int, r_groups: int, pr: int):
    """Pick (block_m, block_c, groups_per_block) with the lane dim at 128
    and the VMEM working set bounded (~2 MiB of int8 planes, twice that
    for bf16 planes: tests/test_tpu_compile.py checks both fit)."""
    block_m = 128 if m >= 128 else max(8, 1 << (m - 1).bit_length())
    block_c = 128 if c >= 128 else max(128, c)   # pad small C up to a lane
    gb = max(1, min(r_groups, max(1, 512 // max(pr, 1))))
    while r_groups % gb:
        gb -= 1
    return block_m, block_c, gb


def _plane_dtype(params: CimMvmParams, pr: int):
    """MXU operand dtype of the kernel's bit planes.

    ``int8`` when every plane fits it (at most 7 bits).  Wider planes
    (an 8-bit DAC or cell) go to ``bfloat16`` with float32 accumulation:
    planes of up to 8 bits are exact in bf16, and one group's analog sum,
    at most ``pr * (2^dac - 1) * (2^cell - 1)``, is exact in f32 below
    2^24.  Mosaic on TPU takes no int32 x int32 matmul.
    """
    if max(params.dac_bits, params.cell_bits) <= 7:
        return jnp.int8
    vmax = pr * ((1 << params.dac_bits) - 1) * ((1 << params.cell_bits) - 1)
    if max(params.dac_bits, params.cell_bits) > 8 or vmax >= 1 << 24:
        raise backend.KernelUnsupportedError(
            f"cim_mvm Pallas kernel: planes of {params.dac_bits}/"
            f"{params.cell_bits} bits over parallel_row={pr} overflow the "
            "exact bf16/f32 MXU range; use mode='xla'")
    return jnp.bfloat16


def _resolve_route(kernel: str, mode: Optional[str], use_kernel,
                   interpret, legacy_use_kernel: bool
                   ) -> backend.KernelRoute:
    """Per-call route resolution, honoring the deprecated boolean kwargs.

    ``legacy_use_kernel`` is the kernel's pre-registry default for
    ``use_kernel`` so the deprecated calling convention keeps its exact
    historical meaning.
    """
    if use_kernel is None and interpret is None:
        return backend.resolve(kernel, mode=mode)
    if mode is not None:
        raise ValueError("pass either mode= or the deprecated "
                         "use_kernel=/interpret= booleans, not both")
    warnings.warn(
        f"{kernel}: use_kernel=/interpret= are deprecated; pass "
        "mode='compiled'|'interpret'|'xla' or let the backend registry "
        "decide (kernels.backend.resolve)",
        DeprecationWarning, stacklevel=3)
    uk = legacy_use_kernel if use_kernel is None else use_kernel
    if not uk:
        legacy = "xla"
    elif interpret is None or interpret:
        legacy = "interpret"
    else:
        legacy = "compiled"
    return backend.resolve(kernel, mode=legacy)


# -- jitted implementations (static route mode) ------------------------------

@functools.partial(jax.jit, static_argnames=("params", "mode"))
def _cim_mvm_impl(x_u: jnp.ndarray, w_u: jnp.ndarray, params: CimMvmParams,
                  mode: str) -> jnp.ndarray:
    if mode == "xla":
        return ref.cim_mvm_ref(
            x_u, w_u, act_bits=params.act_bits,
            weight_bits=params.weight_bits, dac_bits=params.dac_bits,
            cell_bits=params.cell_bits, parallel_row=params.parallel_row,
            adc_bits=params.adc_bits)

    m, r = x_u.shape
    _, c = w_u.shape
    pr = min(params.parallel_row, r)
    n_groups = math.ceil(r / pr)

    # pad rows to a whole number of parallel-row groups
    x_u = _pad_to(x_u.astype(jnp.int32), 1, pr)
    w_u = _pad_to(w_u.astype(jnp.int32), 0, pr)

    xp = ref.bit_planes(x_u, params.act_bits, params.dac_bits)   # (P,M,R')
    ws = ref.bit_planes(w_u, params.weight_bits, params.cell_bits)  # (S,R',C)
    P, S = xp.shape[0], ws.shape[0]

    plane_dtype = _plane_dtype(params, pr)

    block_m, block_c, gb = _block_policy(m, c, n_groups, pr)
    # grouped layouts: (P,G,M,pr) and (S,G,pr,C), padded to the grid
    xpg = xp.reshape(P, -1, n_groups, pr).transpose(0, 2, 1, 3)
    wsg = ws.reshape(S, n_groups, pr, -1)
    xpg = _pad_to(xpg, 2, block_m).astype(plane_dtype)
    wsg = _pad_to(wsg, 3, block_c).astype(plane_dtype)
    # gb was chosen to divide n_groups (_block_policy), no group padding

    out = cim_mvm_pallas(xpg, wsg, dac_bits=params.dac_bits,
                         cell_bits=params.cell_bits,
                         adc_bits=params.adc_bits, block_m=block_m,
                         block_c=block_c, groups_per_block=gb,
                         interpret=(mode == "interpret"))
    return out[:m, :c]


@functools.partial(jax.jit, static_argnames=("params", "mode"))
def _cim_mvm_tiles_impl(x_u: jnp.ndarray, w_u: jnp.ndarray,
                        params: CimMvmParams, mode: str) -> jnp.ndarray:
    if mode == "xla":
        return ref.cim_mvm_ref_tiles(
            x_u, w_u, act_bits=params.act_bits,
            weight_bits=params.weight_bits, dac_bits=params.dac_bits,
            cell_bits=params.cell_bits, parallel_row=params.parallel_row,
            adc_bits=params.adc_bits)

    t, m, r = x_u.shape
    _, _, c = w_u.shape
    pr = min(params.parallel_row, r)
    n_groups = math.ceil(r / pr)

    x_u = _pad_to(x_u.astype(jnp.int32), 2, pr)
    w_u = _pad_to(w_u.astype(jnp.int32), 1, pr)

    xp = ref.bit_planes(x_u, params.act_bits, params.dac_bits)   # (P,T,M,R')
    ws = ref.bit_planes(w_u, params.weight_bits, params.cell_bits)
    P, S = xp.shape[0], ws.shape[0]
    plane_dtype = _plane_dtype(params, pr)

    block_m, block_c, gb = _block_policy(m, c, n_groups, pr)
    # tile-major grouped layouts: (T,P,G,M,pr) and (T,S,G,pr,C)
    xpg = xp.reshape(P, t, m, n_groups, pr).transpose(1, 0, 3, 2, 4)
    wsg = ws.reshape(S, t, n_groups, pr, c).transpose(1, 0, 2, 3, 4)
    xpg = _pad_to(xpg, 3, block_m).astype(plane_dtype)
    wsg = _pad_to(wsg, 4, block_c).astype(plane_dtype)

    out = cim_mvm_tiles_pallas(xpg, wsg, dac_bits=params.dac_bits,
                               cell_bits=params.cell_bits,
                               adc_bits=params.adc_bits, block_m=block_m,
                               block_c=block_c, groups_per_block=gb,
                               interpret=(mode == "interpret"))
    return out[:, :m, :c]


# -- public entry points -----------------------------------------------------

def cim_mvm(x_u: jnp.ndarray, w_u: jnp.ndarray, params: CimMvmParams,
            use_kernel: Optional[bool] = None,
            interpret: Optional[bool] = None, *,
            mode: Optional[str] = None) -> jnp.ndarray:
    """Unsigned crossbar MVM: (M,R) x (R,C) -> (M,C) int32.

    The execution route comes from the backend registry (``compiled``
    pallas_call on TPU/GPU, XLA-compiled oracle on CPU) unless forced
    with ``mode=``; ``use_kernel=``/``interpret=`` are deprecated.
    """
    route = _resolve_route("cim_mvm", mode, use_kernel, interpret, True)
    if x_u.ndim == 1:
        return _cim_mvm_impl(x_u[None], w_u, params, route.mode)[0]
    return _cim_mvm_impl(x_u, w_u, params, route.mode)


def cim_mvm_tiles(x_u: jnp.ndarray, w_u: jnp.ndarray, params: CimMvmParams,
                  use_kernel: Optional[bool] = None,
                  interpret: Optional[bool] = None, *,
                  mode: Optional[str] = None) -> jnp.ndarray:
    """Tile-batched unsigned crossbar MVM: (T,M,R) x (T,R,C) -> (T,M,C).

    The batched entry point used by the trace-lowered executor
    (cimsim.executor): all crossbar tiles of one operator are stacked on
    a leading tile axis and dispatched at once instead of one
    host->device round-trip per tile.  Every tile shares the bit-sliced,
    parallel-row-grouped, ADC-saturating semantics of ``cim_mvm``
    (tiles may be zero-padded along R in the unsigned domain — padding
    preserves per-group ADC values, see ``ref.cim_mvm_ref_tiles``).

    Pallas routes run one ``pallas_call`` whose leading grid dimension
    is the tile axis (``cim_mvm_tiles_pallas``); the ``xla`` route is
    one fused einsum over the tile batch.
    """
    route = _resolve_route("cim_mvm_tiles", mode, use_kernel, interpret,
                           False)
    return _cim_mvm_tiles_impl(x_u, w_u, params, route.mode)


def cim_mvm_signed(x_i: jnp.ndarray, w_i: jnp.ndarray, params: CimMvmParams,
                   use_kernel: Optional[bool] = None,
                   interpret: Optional[bool] = None, *,
                   mode: Optional[str] = None) -> jnp.ndarray:
    """Signed MVM via offset encoding.

    x in [-2^(ab-1), 2^(ab-1)), w likewise; stored as x+ox / w+ow
    unsigned; the rank-1 offset correction is applied digitally (exact
    when the ADC does not saturate — chips budget the ADC for the
    offset-encoded range, and so do our params presets).
    """
    route = _resolve_route("cim_mvm_signed", mode, use_kernel, interpret,
                           True)
    return _cim_mvm_signed_impl(x_i, w_i, params, route.mode)


@functools.partial(jax.jit, static_argnames=("params", "mode"))
def _cim_mvm_signed_impl(x_i: jnp.ndarray, w_i: jnp.ndarray,
                         params: CimMvmParams, mode: str) -> jnp.ndarray:
    squeeze = x_i.ndim == 1
    if squeeze:
        x_i = x_i[None]
    ox = 1 << (params.act_bits - 1)
    ow = 1 << (params.weight_bits - 1)
    x_u = (x_i.astype(jnp.int32) + ox)
    w_u = (w_i.astype(jnp.int32) + ow)
    y_u = _cim_mvm_impl(x_u, w_u, params, mode)
    r = x_i.shape[-1]
    sx = x_u.sum(axis=-1, keepdims=True)          # (M,1)
    sw = w_u.sum(axis=0, keepdims=True)           # (1,C)
    y = y_u - ow * sx - ox * sw + r * ox * ow
    return y[0] if squeeze else y

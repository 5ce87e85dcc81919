"""Trace-lowered batched executor for compiled meta-operator flows.

The op-by-op interpreter (cimsim.functional.FunctionalSimulator) walks
the expanded Program in Python, dispatching one jnp oracle call per
crossbar tile with a host<->device round-trip each time.  This module
lowers a compiled ``(SchedulePlan, Program)`` **once** into a flat
jitted executable with the same bit-exact semantics:

  * ``cim.write_xb`` / ``cim.write_row`` become ahead-of-time weight
    packing: every node's crossbar tiles are sliced out of the weight
    matrix, offset-encoded, and stacked into device-resident arrays
    (``pack``);
  * all ``cim.read_xb`` / ``cim.read_row`` / ``cim.read_core`` ops of a
    node collapse into batched MVM invocations — tiles ride the leading
    tile axis of ``kernels.cim_mvm.cim_mvm_tiles`` (saturating-ADC
    configs), or the whole node folds into a single int32 matmul (the
    provably-exact ADC case);
  * ``shift_acc``, requantization and the DCOM operators are traced
    into the same jnp graph, every one of them the integer rule of
    ``cimsim.functional``'s ALU contract (tables built at lowering), so
    nothing leaves the device inside a dispatch;
  * routed experts (``MoECombine`` and the routed Gemms that feed it)
    multiply only the rows routed to them: per held expert the routed
    token rows are gathered in rounds of a static ``S`` rows (twice the
    expected share, see ``_route_rows``), each round runs the expert's
    whole chain and scatter-adds its gated output; an expert routed more
    than ``S`` rows runs further rounds, in a loop, so no row is
    dropped;
  * every tensor carries a leading batch axis, so N inferences execute
    in one dispatch (``run_batch``);
  * **multi-segment schedules stream weight updates through the trace**:
    when the compile reprograms crossbars between segments (the
    serving stack's time-multiplexed tenants, over-budget workloads),
    the lowering models the physical crossbar pool as per-shape device
    buffers whose contents are swapped at every segment boundary by
    traced updates — each node reads its tiles from the pool state of
    *its* segment, so the jitted program carries the same
    write-then-read dependence chain the hardware does and the
    device-resident weight working set is bounded by the pool, not by
    the sum of all segments' weights.  ``stream="auto"`` (default)
    enables this exactly when ``len(plan.segments) > 1``.

How the MVM itself executes — compiled Pallas kernel, Pallas
interpreter, or the XLA-compiled oracle — is a
``kernels.backend`` registry decision (see ``KernelRoute``); a route
the registry cannot satisfy on the active platform raises
``KernelUnsupportedError`` to the caller.  Nothing falls back to the
interpreter: that runs only when a caller asks for it
(``use_executor=False``).

Lowering is cached process-wide, keyed by the *content* of the compile
(``compiler.compile_key_for_plan``) x the crossbar compute params — a
calibration loop or verification sweep pays tracing once.  Weights and
requantization shifts are runtime inputs, not baked constants: the same
executable serves any weight set (re-``pack``) and any shift table.

The interpreter remains the bit-exact oracle; tests sweep the executor
against it across chip modes, saturating-ADC configs and batch sizes.
"""
from __future__ import annotations

import contextlib
import dataclasses
import time
from collections import OrderedDict
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..obs import metrics as obs_metrics
from ..obs import trace as obs_trace
from ..core.abstraction import CIMArch
from ..core.cg_opt import OpPlacement, SchedulePlan
from ..core.graph import Graph, Node, route_share, weight_matrix_shape
from ..core.mop import Program
from ..kernels import backend
from ..kernels.cim_mvm import CimMvmParams, cim_mvm_params
from ..kernels.cim_mvm.ops import _cim_mvm_tiles_impl
from . import functional as fn
from .functional import chunk_offsets, spread_slice, tile_ranges

_INT32_MAX = 2 ** 31 - 1

#: rows of one split-plane f32 GEMM of the exact-ADC path: per-plane
#: |partial| <= R * 128 * 15 must stay under 2^24 (the f32 exact-integer
#: range), so R <= 8192 is safe; taller matrices sum such chunks in int32
_F32_SPLIT_MAX_R = 8192

#: DCOM graph ops the lowering can trace (parity with apply_dcom).
_SUPPORTED_DCOM = {
    "Relu", "Add", "Mul", "MaxPool", "AveragePool", "GlobalAveragePool",
    "Flatten", "Reshape", "Identity", "Transpose", "Concat", "Split",
    "MatMul", "Gelu", "Silu", "Sigmoid", "Tanh", "Softmax", "LayerNorm",
    "RMSNorm", "RoPE", "TopKRouter", "MoECombine",
}

#: ops whose lowering consumes a calibrated requantization shift
_SHIFTED_DCOM = {"Add", "Mul", "MatMul", "MoECombine"}

#: ops a routed expert's chain may hold between its routed Gemms: row-wise,
#: so a round of gathered rows computes them as the whole tensor would
_ROWWISE = {"Relu", "Gelu", "Silu", "Sigmoid", "Tanh", "Mul", "Add"}

#: contraction block of an ALU MatMul: int8-range planes summed over 1024
#: products stay within 2**24, the f32 exact-integer range
_MATMUL_BLOCK = 1024

#: rows of one routing round per held expert, as a multiple of the
#: expected routed share of the dispatch's token rows
_ROUTE_SLACK = 2

#: key of the routed-rows counter among a routed program's device outputs
ROUTED_ROWS = "__routed_rows__"


def _scopes(node: Node):
    """The ``jax.named_scope``s of a node's ops: the parts of its
    ``scope`` attr (the block it belongs to, e.g. ``mla/attn``), then its
    name."""
    import jax
    stack = contextlib.ExitStack()
    for part in filter(None, node.attrs.get("scope", "").split("/")):
        stack.enter_context(jax.named_scope(part))
    stack.enter_context(jax.named_scope(node.name))
    return stack


def _select(table: np.ndarray, idx):
    """``table[idx]`` for a small constant table, as a tree of selects on
    the bits of ``idx`` (an index gather is slow on the device); equal
    neighbours fold into one constant, and an int8 table selects int8
    values (a quarter of the bytes wherever XLA keeps a level)."""
    import jax.numpy as jnp
    dt = jnp.int8 if table.min() >= -128 and table.max() <= 127 \
        else jnp.int32
    vals: List[Any] = [int(v) for v in table]
    bit = 0
    while len(vals) > 1:
        on = ((idx >> bit) & 1) == 1
        vals = [a if isinstance(a, int) and isinstance(b, int) and a == b
                else jnp.where(on, jnp.asarray(b, dt), jnp.asarray(a, dt))
                for a, b in zip(vals[0::2], vals[1::2])]
        bit += 1
    return jnp.full(idx.shape, vals[0], jnp.int32) \
        if isinstance(vals[0], int) else vals[0].astype(jnp.int32)


def _isqrt(n):
    """floor(sqrt(n)) of int32 ``n`` in [0, 2**30], exactly and without a
    float root (the device's may be approximate): set the root's 16 bits
    from the top, keeping a bit where ``c <= n // c``, i.e. ``c*c <= n``."""
    import jax.numpy as jnp
    r = jnp.zeros_like(n)
    for bit in reversed(range(16)):
        c = r | (1 << bit)
        r = jnp.where(c <= n // c, c, r)
    return r


class LoweringError(ValueError):
    """The program cannot be trace-lowered bit-exactly (unsupported op,
    int32 overflow risk, incomplete crossbar coverage)."""


def _resolve_executor_route(route: Optional[backend.KernelRoute],
                            mode: Optional[str],
                            use_kernel: Optional[bool],
                            interpret: Optional[bool]
                            ) -> backend.KernelRoute:
    """The executor's MVM route, resolved by the backend registry; an
    unsupportable request raises ``KernelUnsupportedError``.

    ``use_kernel``/``interpret`` keep the pre-registry boolean calling
    convention alive (executor legacy default was the oracle path).
    """
    if use_kernel is not None or interpret is not None:
        uk = bool(use_kernel)            # legacy default: False
        legacy = "xla" if not uk else \
            ("compiled" if interpret is False else "interpret")
        return backend.resolve("cim_mvm_tiles", mode=legacy)
    if route is not None:
        return route
    return backend.resolve("cim_mvm_tiles", mode=mode)


@dataclasses.dataclass
class ExecutorStats:
    """Lowering statistics (shape of the flattened program)."""

    cim_nodes: int = 0
    dcom_nodes: int = 0
    units: int = 0          # crossbar read units folded into dispatches
    dispatches: int = 0     # batched MVM invocations in the traced graph
    matmul_nodes: int = 0   # exact-ADC nodes lowered to one int matmul
    segments: int = 1       # schedule segments of the compiled plan
    streamed: bool = False  # weight-update streaming active (multi-segment)
    swaps: int = 0          # traced segment-boundary weight-pool updates
    kernel_mode: str = ""   # resolved cim_mvm_tiles route (backend registry)

    @property
    def cim_reads(self) -> int:   # SimStats-compatible accessor
        return self.units


@dataclasses.dataclass(frozen=True)
class _Bucket:
    """Same-shaped crossbar tiles of one node, batched into one call."""

    spans: Tuple[Tuple[int, int, int, int], ...]   # (r0, r1, c0, c1) per tile
    r_len: int
    c_len: int

    @property
    def key(self) -> str:
        return f"{self.r_len}x{self.c_len}"


@dataclasses.dataclass(frozen=True)
class _StreamGroup:
    """Same-shaped tiles of one node living in one schedule segment.

    The streamed twin of ``_Bucket``: tiles are not packed per node but
    occupy slots ``[lo, hi)`` of the shared per-shape crossbar pool for
    the duration of segment ``seg`` — the node's dispatch slices them
    out of that segment's pool state.
    """

    seg: int
    spans: Tuple[Tuple[int, int, int, int], ...]
    r_len: int
    c_len: int
    lo: int                          # first pool slot (static)
    hi: int                          # one past the last pool slot

    @property
    def key(self) -> str:
        return f"{self.r_len}x{self.c_len}"


@dataclasses.dataclass
class _CimPlan:
    """Static lowering of one CIM node."""

    node: Node
    r: int
    c: int
    exact: bool                      # single-matmul path (ADC never clips)
    buckets: List[_Bucket]
    vector_in: bool                  # unbatched input was 1-D
    conv_out: Optional[Tuple[int, int, int]] = None   # (cout, oh, ow)
    window: Optional[Tuple[int, int, int]] = None     # conv (k, stride, pad)
    stream_groups: Tuple[_StreamGroup, ...] = ()      # streamed mode only


def _patches(x, k: int, stride: int, pad: int):
    """(N, C, H, W) -> functional.im2col's (N, OH*OW, C*k*k) patch matrix,
    stacked from the k*k static strided slices of the padded input: no
    index table, no gather (jnp's stepped indexing would lower to one)."""
    import jax.numpy as jnp
    from jax import lax
    x = jnp.pad(x, ((0, 0), (0, 0), (pad, pad), (pad, pad)))
    n, c, hp, wp = x.shape
    taps = [lax.slice(x, (0, 0, di, dj), (n, c, hp - k + 1 + di,
                                          wp - k + 1 + dj),
                      (1, 1, stride, stride))
            for di in range(k) for dj in range(k)]      # (N, C, OH, OW) each
    cols = jnp.stack(taps, axis=2)                      # (N, C, k*k, OH, OW)
    return cols.reshape(n, c * k * k, -1).transpose(0, 2, 1)


def _pool_indices(h: int, w: int, k: int, stride: int, pad: int
                  ) -> np.ndarray:
    """(OH*OW, k*k) gather indices into a flattened padded (Hp,Wp) map."""
    wp = w + 2 * pad
    oh = (h + 2 * pad - k) // stride + 1
    ow = (w + 2 * pad - k) // stride + 1
    di, dj = np.meshgrid(np.arange(k), np.arange(k), indexing="ij")
    win = (di * wp + dj).reshape(-1)
    ii, jj = np.meshgrid(np.arange(oh) * stride, np.arange(ow) * stride,
                         indexing="ij")
    base = (ii * wp + jj).reshape(-1)
    return (base[:, None] + win[None, :]).astype(np.int32)


def _collect_units(program: Program, placements: Dict[Tuple[str, int],
                                                      OpPlacement],
                   graph: Graph, arch: CIMArch,
                   seg_of: Dict[Tuple[str, int], int]
                   ) -> Dict[str, List[Tuple[Tuple[int, int, int, int], int]]]:
    """Walk the (possibly Loop-compressed) program once and resolve every
    distinct crossbar read into a weight-matrix span (r0, r1, c0, c1)
    tagged with the schedule segment its chunk is placed in.

    Copies and windows are emission-side parallelism: every copy reads
    the same tiles and each window row is handled by exactly one copy,
    so the executor applies each distinct unit to *all* window rows.
    """
    seen: Dict[Tuple, None] = {}
    for op in program.walk(expand_loops=False):
        k = op.kind
        if k == "cim.read_core":
            seen.setdefault(("core", op.attrs["node"],
                             op.attrs.get("chunk", 0)))
        elif k in ("cim.read_xb", "cim.read_row"):
            a = op.attrs
            seen.setdefault((k, a["op"], a.get("chunk", 0),
                             a.get("row_tile", 0), a.get("col_tile", 0),
                             a.get("spread", 0)))
    units: Dict[str, List[Tuple[Tuple[int, int, int, int], int]]] = {}
    for key in seen:
        if key[0] == "core":
            _, name, chunk = key
            node = graph.node(name)
            p = placements[(name, chunk)]
            total_r, total_c = weight_matrix_shape(node)
            ro, co = chunk_offsets(node, p)
            span = (ro, min(ro + p.mapping.r, total_r),
                    co, min(co + p.mapping.c, total_c))
        else:
            kind, name, chunk, rt, ct, spread = key
            node = graph.node(name)
            p = placements[(name, chunk)]
            total_r, total_c = weight_matrix_shape(node)
            r0, r1, c0, c1 = tile_ranges(p, arch, rt, ct)
            ro, co = chunk_offsets(node, p)
            r_lo, r_hi = ro + r0, min(ro + r1, total_r)
            c_lo, c_hi = co + c0, min(co + c1, total_c)
            if r_hi <= r_lo or c_hi <= c_lo:
                continue
            if kind == "cim.read_row" and p.row_spread > 1:
                ss = spread_slice(r_hi - r_lo, arch.xb.parallel_row,
                                  p.row_spread, spread)
                if ss is None:
                    continue
                r_lo, r_hi = r_lo + ss[0], r_lo + ss[1]
            span = (r_lo, r_hi, c_lo, c_hi)
        if span[1] > span[0] and span[3] > span[2]:
            units.setdefault(name, []).append(
                (span, seg_of.get((name, key[2]), 0)))
    return units


class LoweredExecutable:
    """One compiled program, trace-lowered to a jitted batched function.

    Construction is pure analysis (no tracing); jax traces lazily on the
    first ``run``/``run_batch`` per batch shape.  Weights enter through
    ``pack`` (ahead-of-time tile packing) and shifts are per-call scalar
    inputs, so neither forces a re-trace.
    """

    def __init__(self, plan: SchedulePlan, program: Program,
                 params: Optional[CimMvmParams] = None, *,
                 mode: Optional[str] = None,
                 stream="auto",
                 route: Optional[backend.KernelRoute] = None,
                 use_kernel: Optional[bool] = None,
                 interpret: Optional[bool] = None,
                 faults=None):
        import jax
        self.plan = plan
        #: optional cimsim.faults.FaultMap — tile weight transforms fold
        #: into ``pack`` and the per-tile post-MVM ADC offsets become
        #: trace constants, so the jitted program stays one program
        self.faults = faults
        self.graph: Graph = plan.graph
        self.arch: CIMArch = plan.arch
        self.params = params or cim_mvm_params(plan.arch)
        self.route = _resolve_executor_route(route, mode, use_kernel,
                                             interpret)
        self._n_segments = max(1, len(plan.segments))
        if stream == "auto":
            stream = self._n_segments > 1
        self._stream = bool(stream)
        self.stats = ExecutorStats(segments=self._n_segments,
                                   streamed=self._stream,
                                   kernel_mode=self.route.mode)
        #: compile-key prefix linking this executable back to the span
        #: the compiler drew (set by ``lower`` when tracing is on); the
        #: first dispatch closes the compile→dispatch flow arrow
        self._flow_key: Optional[str] = None
        self._flow_done = False
        #: bound metric instruments for the dispatch hot path, cached
        #: per registry identity so a dispatch pays attribute access +
        #: a float add instead of two label-key constructions
        self._prof: Optional[tuple] = None
        self._disp_span = f"dispatch:{self.graph.name}"
        self._ox = 1 << (self.params.act_bits - 1)
        self._ow = 1 << (self.params.weight_bits - 1)

        unsupported = sorted({n.op_type for n in self.graph.nodes
                              if not n.is_cim
                              and n.op_type not in _SUPPORTED_DCOM})
        if unsupported:
            raise LoweringError(f"no bit-exact lowering for {unsupported}")

        seg_of = {(p.node.name, p.chunk): si
                  for si, seg in enumerate(plan.segments)
                  for p in seg.placements}
        placements = {(p.node.name, p.chunk): p for p in plan.placements}
        units = _collect_units(program, placements, self.graph, self.arch,
                               seg_of)
        #: streamed-mode crossbar-pool layout: per (segment, shape key)
        #: the tiles resident there, in slot order (drives ``pack``)
        self._seg_layout: Dict[Tuple[int, str],
                               List[Tuple[str, Tuple[int, int, int, int]]]] \
            = {}
        self._seg_cursor: Dict[Tuple[int, str], int] = {}
        self._plans: Dict[str, _CimPlan] = {}
        for node in self.graph.cim_nodes:
            self._plans[node.name] = self._lower_cim_node(node,
                                                          units.get(node.name))
        #: per-shape pool depth = the largest simultaneous (per-segment)
        #: tile count — the device working set a real crossbar pool holds
        self._pool_shapes: Dict[str, Tuple[int, int, int]] = {}
        for (seg, key), n in self._seg_cursor.items():
            rl, cl = (int(v) for v in key.split("x"))
            depth = max(n, self._pool_shapes.get(key, (0,))[0])
            self._pool_shapes[key] = (depth, rl, cl)
        self.stats.swaps = len(self._seg_layout)
        self._build_fault_offsets()
        self._lower_alu()
        self._pool_idx: Dict[str, np.ndarray] = {}
        for node in self.graph.nodes:
            if node.op_type in ("MaxPool", "AveragePool"):
                _, h, w = self.graph.shapes[node.inputs[0]]
                k = node.attrs.get("kernel", 2)
                self._pool_idx[node.name] = _pool_indices(
                    h, w, k, node.attrs.get("stride", k),
                    node.attrs.get("pad", 0))
            if not node.is_cim:
                self.stats.dcom_nodes += 1
        self._shift_names = sorted(
            [n.name for n in self.graph.nodes
             if n.is_cim or n.op_type in _SHIFTED_DCOM])
        self._jit = jax.jit(self._forward)

    # -- lowering ---------------------------------------------------------
    def _lower_cim_node(self, node: Node,
                        tagged: Optional[Sequence[Tuple[
                            Tuple[int, int, int, int], int]]]
                        ) -> _CimPlan:
        total_r, total_c = weight_matrix_shape(node)
        if not tagged:
            raise LoweringError(f"{node.name}: no crossbar reads emitted")
        spans = [span for span, _ in tagged]
        covered = sum((r1 - r0) * (c1 - c0) for r0, r1, c0, c1 in spans)
        if covered != total_r * total_c:
            raise LoweringError(
                f"{node.name}: crossbar reads cover {covered} weight cells, "
                f"expected {total_r * total_c}")
        # int32 headroom: the signed accumulator is bounded by R*2^(ab+wb-2)
        # and each unit's unsigned partial by r_u*(2^ab-1)*(2^wb-1)
        ab, wb = self.params.act_bits, self.params.weight_bits
        max_r_u = max(r1 - r0 for r0, r1, _, _ in spans)
        if (total_r << (ab + wb - 2)) > _INT32_MAX or \
                max_r_u * ((1 << ab) - 1) * ((1 << wb) - 1) > _INT32_MAX:
            raise LoweringError(f"{node.name}: accumulation exceeds int32")

        by_shape: Dict[Tuple[int, int], List[Tuple[int, int, int, int]]] = {}
        for span in sorted(spans):
            r0, r1, c0, c1 = span
            by_shape.setdefault((r1 - r0, c1 - c0), []).append(span)
        buckets = [_Bucket(spans=tuple(group), r_len=rl, c_len=cl)
                   for (rl, cl), group in sorted(by_shape.items())]

        stream_groups: Tuple[_StreamGroup, ...] = ()
        if self._stream:
            # streamed mode: tiles live in the shared per-shape crossbar
            # pool only for their segment — group per (segment, shape)
            # and claim contiguous slots from that segment's cursor
            by_ss: Dict[Tuple[int, int, int],
                        List[Tuple[int, int, int, int]]] = {}
            for span, seg in sorted(tagged, key=lambda t: (t[1], t[0])):
                r0, r1, c0, c1 = span
                by_ss.setdefault((seg, r1 - r0, c1 - c0), []).append(span)
            groups = []
            for (seg, rl, cl), group in sorted(by_ss.items()):
                key = f"{rl}x{cl}"
                lo = self._seg_cursor.get((seg, key), 0)
                hi = lo + len(group)
                self._seg_cursor[(seg, key)] = hi
                self._seg_layout.setdefault((seg, key), []).extend(
                    (node.name, s) for s in group)
                groups.append(_StreamGroup(seg=seg, spans=tuple(group),
                                           r_len=rl, c_len=cl, lo=lo,
                                           hi=hi))
            stream_groups = tuple(groups)

        # streamed mode always rides the tile path: the pool models
        # physical crossbar residency, which the whole-matrix matmul
        # shortcut would bypass
        exact = self.params.exact and not self._stream
        self.stats.cim_nodes += 1
        self.stats.units += len(spans)
        self.stats.dispatches += len(stream_groups) if self._stream \
            else (1 if exact else len(buckets))
        self.stats.matmul_nodes += int(exact)

        cp = _CimPlan(node=node, r=total_r, c=total_c, exact=exact,
                      buckets=buckets,
                      vector_in=len(self.graph.shapes[node.inputs[0]]) == 1,
                      stream_groups=stream_groups)
        if node.op_type == "Conv":
            cp.window = (node.attrs["weight_shape"][2],
                         node.attrs.get("stride", 1), node.attrs.get("pad", 0))
            cout = node.attrs["weight_shape"][0]
            oh, ow = self.graph.shapes[node.outputs[0]][1:]
            cp.conv_out = (cout, oh, ow)
        return cp

    # -- fault folding ----------------------------------------------------
    def _build_fault_offsets(self) -> None:
        """Precompute the fault map's post-MVM ADC-offset terms as trace
        constants, one per dispatch shape:

          * exact path — a per-node (C,) aggregate (each tile span's
            offset lands once per window row, and the spans partition
            the matrix, so columns simply sum over their row tiles);
          * bucket / stream paths — a (T, 1, c_len) stack matching the
            tile axis of the batched MVM.

        The interpreter adds ``tile_offset(name, span)`` to every span's
        partial sum; these are the same vectors pre-folded per shape.
        """
        self._off_exact: Dict[str, Optional[np.ndarray]] = {}
        self._off_bucket: Dict[Tuple[str, str], Optional[np.ndarray]] = {}
        self._off_stream: Dict[Tuple[str, int], Optional[np.ndarray]] = {}
        if self.faults is None:
            return

        def stack(spans):
            offs = [self.faults.tile_offset(name, s) for s in spans]
            if all(o is None for o in offs):
                return None
            c_len = spans[0][3] - spans[0][2]
            return np.stack(
                [np.zeros(c_len, np.int64) if o is None else o
                 for o in offs]).astype(np.int32)[:, None, :]

        for name, cp in self._plans.items():
            if cp.exact:
                off = np.zeros(cp.c, np.int64)
                any_off = False
                for b in cp.buckets:
                    for s in b.spans:
                        t = self.faults.tile_offset(name, s)
                        if t is not None:
                            off[s[2]:s[3]] += t
                            any_off = True
                self._off_exact[name] = \
                    off.astype(np.int32) if any_off else None
            elif self._stream:
                for gi, g in enumerate(cp.stream_groups):
                    self._off_stream[(name, gi)] = stack(g.spans)
            else:
                for b in cp.buckets:
                    self._off_bucket[(name, b.key)] = stack(b.spans)

    def _fault_tiles(self, name: str, spans, w: np.ndarray) -> np.ndarray:
        """Stack tile ``spans`` of signed matrix ``w``, applying the
        fault map's per-tile weight transform when one is active."""
        if self.faults is None:
            return np.stack([w[r0:r1, c0:c1] for r0, r1, c0, c1 in spans])
        return np.stack(
            [self.faults.apply_tile(name, s, w[s[0]:s[1], s[2]:s[3]])
             for s in spans])

    # -- weight packing ---------------------------------------------------
    def pack(self, weights: Dict[str, np.ndarray]) -> Dict[str, Any]:
        """Ahead-of-time weight programming: the ``cim.write_*`` ops.

        Exact-ADC nodes keep their signed (R, C) matrix; saturating
        configs get offset-encoded tile stacks plus the rank-1 column
        sums of the digital offset correction.

        Streamed (multi-segment) mode instead packs one offset-encoded
        tile stack **per (segment, tile shape)** in crossbar-pool slot
        order — the payloads the traced segment-boundary swaps write
        into the pool buffers.
        """
        name = self.graph.name
        t0 = time.perf_counter()
        with obs_trace.span("cim.executor.pack", obs_trace.EXECUTOR_TRACK,
                            name, event=f"pack:{name}", cat="executor",
                            segments=self._n_segments,
                            streamed=self._stream) as sp:
            packed = self._pack_impl(weights)
            if sp:
                sp.args["bytes"] = _packed_nbytes(packed)
        obs_metrics.observe("executor_pack_s", time.perf_counter() - t0)
        return packed

    def _pack_impl(self, weights: Dict[str, np.ndarray]) -> Dict[str, Any]:
        import jax.numpy as jnp
        if self._stream:
            mats: Dict[str, np.ndarray] = {}
            for name, cp in self._plans.items():
                w = np.asarray(weights[name], np.int32)
                if w.shape != (cp.r, cp.c):
                    raise ValueError(f"{name}: weights {w.shape} != "
                                     f"{(cp.r, cp.c)}")
                mats[name] = w
            segs: List[Dict[str, Any]] = []
            for si in range(self._n_segments):
                entry = {}
                for (seg, key), layout in self._seg_layout.items():
                    if seg != si:
                        continue
                    if self.faults is None:
                        tiles = np.stack(
                            [mats[name][r0:r1, c0:c1]
                             for name, (r0, r1, c0, c1) in layout])
                    else:
                        tiles = np.stack(
                            [self.faults.apply_tile(
                                name, span,
                                mats[name][span[0]:span[1],
                                           span[2]:span[3]])
                             for name, span in layout])
                    entry[key] = jnp.asarray(tiles + self._ow)   # unsigned
                segs.append(entry)
            return {"segs": segs}
        packed: Dict[str, Any] = {}
        for name, cp in self._plans.items():
            w = np.asarray(weights[name], np.int32)
            if w.shape != (cp.r, cp.c):
                raise ValueError(f"{name}: weights {w.shape} != "
                                 f"{(cp.r, cp.c)}")
            if cp.exact:
                if self.faults is not None:
                    # tile spans partition the matrix (coverage is
                    # checked at lowering), so per-span surgery yields
                    # the full effective matrix; values stay in the
                    # signed weight range, keeping the split-plane GEMM
                    # exact
                    w = w.copy()
                    for b in cp.buckets:
                        for s in b.spans:
                            w[s[0]:s[1], s[2]:s[3]] = \
                                self.faults.apply_tile(
                                    name, s, w[s[0]:s[1], s[2]:s[3]])
                if self.params.act_bits <= 8 and self.params.weight_bits <= 8:
                    # split-plane GEMM: w = 16*w_hi + w_lo with w_hi in
                    # [-8,7], w_lo in [0,15]; planes and int8 activations
                    # are exact in bf16, and each f32 partial product sum
                    # over at most _F32_SPLIT_MAX_R rows stays under 2^24,
                    # so the fast float GEMM is exact
                    packed[name] = {"hi": jnp.asarray((w >> 4), jnp.bfloat16),
                                    "lo": jnp.asarray((w & 15), jnp.bfloat16)}
                else:
                    packed[name] = {"w": jnp.asarray(w)}
                continue
            entry: Dict[str, Any] = {}
            for b in cp.buckets:
                tiles = self._fault_tiles(name, b.spans, w)
                w_u = tiles + self._ow                       # unsigned
                entry[b.key] = {
                    "w": jnp.asarray(w_u),
                    "sw": jnp.asarray(w_u.sum(axis=1, keepdims=True,
                                              dtype=np.int32)),
                }
            packed[name] = entry
        return packed

    # -- execution --------------------------------------------------------
    def run(self, inputs: Dict[str, np.ndarray],
            weights: Optional[Dict[str, np.ndarray]] = None,
            shifts: Optional[Dict[str, int]] = None, *,
            packed: Optional[Dict[str, Any]] = None
            ) -> Dict[str, np.ndarray]:
        """One inference on unbatched inputs (batch axis added/stripped)."""
        batched = {k: np.asarray(v)[None] for k, v in inputs.items()}
        out = self.run_batch(batched, weights, shifts, packed=packed)
        return {k: v[0] for k, v in out.items()}

    def run_batch(self, inputs: Dict[str, np.ndarray],
                  weights: Optional[Dict[str, np.ndarray]] = None,
                  shifts: Optional[Dict[str, int]] = None, *,
                  packed: Optional[Dict[str, Any]] = None
                  ) -> Dict[str, np.ndarray]:
        """N inferences in one dispatch: every input carries a leading
        batch axis.  Pass ``packed=self.pack(weights)`` to amortize
        weight packing across calls.

        Profiling happens here, at the dispatch boundary, and inside it
        at the host's three steps: ``cim.executor.put`` (shifts and
        inputs to the device), ``cim.executor.run`` (the jitted program,
        until its outputs are ready) and ``cim.executor.fetch`` (outputs
        to the host).  The jitted trace stays one program, so per-node
        device times come from the profiler's device trace, where the
        ops carry each node's ``jax.named_scope``.  Disabled telemetry
        costs two ``is None`` checks here and one per step.
        """
        reg = obs_metrics.active()
        if reg is None and not obs_trace.spans_on():
            return self._run_batch_impl(inputs, weights, shifts,
                                        packed=packed)
        n = int(next(iter(inputs.values())).shape[0]) if inputs else 0
        name = self.graph.name
        t0 = time.perf_counter()
        with obs_trace.span("cim.executor.dispatch",
                            obs_trace.EXECUTOR_TRACK, name,
                            event=self._disp_span, cat="executor", batch=n,
                            route=self.route.mode,
                            segments=self._n_segments,
                            swaps=self.stats.swaps) as sp:
            out = self._run_batch_impl(inputs, weights, shifts,
                                       packed=packed)
        dt = time.perf_counter() - t0
        if reg is not None:
            prof = self._prof
            if prof is None or prof[0] is not reg:
                prof = self._prof = (
                    reg,
                    reg.counter("executor_dispatches_total",
                                route=self.route.mode),
                    reg.histogram("executor_dispatch_s",
                                  route=self.route.mode))
            prof[1].inc()
            prof[2].observe(dt)
        tr = obs_trace.get_trace()
        if tr is not None and sp and self._flow_key is not None \
                and not self._flow_done:
            # close the compile→dispatch arrow inside this span
            self._flow_done = True
            tr.flow_end(obs_trace.EXECUTOR_TRACK, name, "artifact",
                        "flow", sp.ts_s + sp.dur_s / 2,
                        flow_id=int(self._flow_key[:12], 16),
                        key=self._flow_key[:12])
        return out

    def _run_batch_impl(self, inputs, weights=None, shifts=None, *,
                        packed=None) -> Dict[str, np.ndarray]:
        import jax
        import jax.numpy as jnp
        if packed is None:
            if weights is None:
                raise ValueError("need weights=... or packed=...")
            packed = self.pack(weights)
        shifts = shifts or {}
        span, track, wl = obs_trace.span, obs_trace.EXECUTOR_TRACK, \
            self.graph.name
        # with spans on, put and run end on the device's readiness, so
        # that each step's span holds its own device time
        with span("cim.executor.put", track, wl) as sp:
            sh = {name: jnp.int32(shifts.get(name, 0))
                  for name in self._shift_names}
            xs = {name: jnp.asarray(np.asarray(v), jnp.int32)
                  for name, v in inputs.items()}
            if sp:
                jax.block_until_ready((sh, xs))
        with span("cim.executor.run", track, wl) as sp:
            out = self._jit(packed, sh, xs)
            if sp:
                jax.block_until_ready(out)
        with span("cim.executor.fetch", track, wl):
            out = {name: np.asarray(v) for name, v in out.items()}
        rows = out.pop(ROUTED_ROWS, None)
        if rows is not None:
            self._count_routed(rows)
        return out

    def _count_routed(self, rows: np.ndarray) -> None:
        """The program counter ``executor_routed_rows_total{node,expert}``:
        token rows routed to each held expert, summed over dispatches."""
        if obs_metrics.active() is None:
            return
        for name, per_expert in zip(self._combines, rows):
            for j, n in enumerate(per_expert):
                obs_metrics.count("executor_routed_rows_total", float(n),
                                  node=name, expert=j)

    # -- the traced program ----------------------------------------------
    def _swap_chain(self, segs):
        """Trace the segment-boundary weight swaps: one pool state per
        segment, each produced from the previous by in-place ``.at``
        updates — the jitted program carries the hardware's
        write-then-read dependence chain and holds at most the pool
        (not the sum of all segments' tiles) on device."""
        import jax.numpy as jnp
        cur = {key: jnp.zeros(shape, jnp.int32)
               for key, shape in self._pool_shapes.items()}
        states = []
        for entry in segs:
            cur = dict(cur)
            for key, w in entry.items():
                cur[key] = cur[key].at[:w.shape[0]].set(w)
            states.append(cur)
        return states

    def _forward(self, packed, shifts, inputs):
        """The traced program.  Each node's ops carry the node's name as
        a ``jax.named_scope`` (and a CIM node's, its phase: ``im2col``,
        ``gemm``, ``requant``), which the profiler's device trace shows
        as each op's name path; the scopes are metadata only."""
        import jax
        pools = None
        if self._stream:
            with jax.named_scope("swap"):
                pools = self._swap_chain(packed["segs"])
        tensors: Dict[str, Any] = dict(inputs)
        routed: List[Any] = []
        for node in self.graph.nodes:
            if node.name in self._in_region:
                continue             # runs inside its MoECombine's rounds
            with _scopes(node):
                if node.op_type == "MoECombine":
                    y, rows = self._combine(node, tensors, packed, shifts,
                                            pools)
                    tensors[node.outputs[0]] = y
                    routed.append(rows)
                    continue
                xs = [tensors[t] for t in node.inputs]
                if node.is_cim:
                    pw = None if self._stream else packed[node.name]
                    tensors[node.outputs[0]] = self._cim(
                        node, xs[0], pw, shifts[node.name], pools)
                elif node.op_type == "Split":
                    for name, part in zip(node.outputs,
                                          self._split(node, xs[0])):
                        tensors[name] = part
                else:
                    tensors[node.outputs[0]] = self._dcom(node, xs, shifts)
        out = {t: tensors[t] for t in self.graph.outputs}
        if routed:
            import jax.numpy as jnp
            out[ROUTED_ROWS] = jnp.stack(routed)
        return out

    def _rows(self, node: Node, x):
        """(N, windows, R) MVM input rows (im2col for Conv)."""
        cp = self._plans[node.name]
        if cp.window is not None:
            return _patches(x, *cp.window)
        return x[:, None, :] if cp.vector_in else x

    def _cim(self, node: Node, x, pw, sh, pools=None):
        import jax
        cp = self._plans[node.name]
        with jax.named_scope("im2col"):
            if cp.exact and "hi" in pw:
                # the split-plane GEMM's operand type, before the k*k
                # times larger patch matrix exists
                x = x.astype(pw["hi"].dtype)
            rows = self._rows(node, x)                 # (N, M, R)
        with jax.named_scope("gemm"):
            acc = self._mvm(node, rows, pw, pools)     # (N, M, C)
        with jax.named_scope("requant"):
            return self._requant(cp, acc, sh)

    def _mvm(self, node: Node, rows, pw, pools):
        """(N, M, C) int32 accumulator of one CIM node's crossbar reads."""
        import jax.numpy as jnp
        cp = self._plans[node.name]
        n, m, _ = rows.shape
        if cp.exact:
            if "hi" in pw:
                parts = []
                for r0 in range(0, cp.r, _F32_SPLIT_MAX_R):
                    r1 = min(cp.r, r0 + _F32_SPLIT_MAX_R)
                    one = cp.r <= _F32_SPLIT_MAX_R
                    xr = rows if one else rows[..., r0:r1]
                    hi, lo = (jnp.matmul(xr, pw[p] if one else pw[p][r0:r1],
                                         preferred_element_type=jnp.float32)
                              for p in ("hi", "lo"))
                    parts.append((hi.astype(jnp.int32) << 4)
                                 + lo.astype(jnp.int32))
                acc = sum(parts[1:], parts[0])
            else:
                acc = jnp.matmul(rows, pw["w"],
                                 preferred_element_type=jnp.int32)
            off = self._off_exact.get(node.name)
            if off is not None:
                acc = acc + off
        elif self._stream:
            flat = (rows + self._ox).reshape(n * m, cp.r)
            acc = jnp.zeros((n * m, cp.c), jnp.int32)
            for gi, g in enumerate(cp.stream_groups):
                rows_idx = np.stack([np.arange(r0, r1, dtype=np.int32)
                                     for r0, r1, _, _ in g.spans])
                xt = jnp.moveaxis(flat[:, rows_idx], 1, 0)  # (T, NM, r_len)
                # tiles come out of *this segment's* pool state, so the
                # dispatch depends on the traced swap chain; the offset
                # correction's column sums are recomputed in-trace
                w_u = pools[g.seg][g.key][g.lo:g.hi]
                sw = w_u.sum(axis=1, keepdims=True)
                y_u = _cim_mvm_tiles_impl(xt, w_u, self.params,
                                          self.route.mode)
                sx = xt.sum(-1, keepdims=True)
                y = (y_u - self._ow * sx - self._ox * sw
                     + g.r_len * self._ox * self._ow)
                off = self._off_stream.get((node.name, gi))
                if off is not None:
                    y = y + off
                col_idx = np.concatenate(
                    [np.arange(c0, c1, dtype=np.int32)
                     for _, _, c0, c1 in g.spans])
                acc = acc.at[:, col_idx].add(
                    jnp.moveaxis(y, 0, 1).reshape(n * m, -1))
            acc = acc.reshape(n, m, cp.c)
        else:
            flat = (rows + self._ox).reshape(n * m, cp.r)
            acc = jnp.zeros((n * m, cp.c), jnp.int32)
            for b in cp.buckets:
                rows_idx = np.stack([np.arange(r0, r1, dtype=np.int32)
                                     for r0, r1, _, _ in b.spans])
                xt = jnp.moveaxis(flat[:, rows_idx], 1, 0)  # (T, NM, r_len)
                y_u = _cim_mvm_tiles_impl(xt, pw[b.key]["w"], self.params,
                                          self.route.mode)
                sx = xt.sum(-1, keepdims=True)
                y = (y_u - self._ow * sx - self._ox * pw[b.key]["sw"]
                     + b.r_len * self._ox * self._ow)
                off = self._off_bucket.get((node.name, b.key))
                if off is not None:
                    y = y + off
                col_idx = np.concatenate(
                    [np.arange(c0, c1, dtype=np.int32)
                     for _, _, c0, c1 in b.spans])
                acc = acc.at[:, col_idx].add(
                    jnp.moveaxis(y, 0, 1).reshape(n * m, -1))
            acc = acc.reshape(n, m, cp.c)
        return acc

    @staticmethod
    def _requant(cp: _CimPlan, acc, sh):
        """Shift, clip to int8 range, and lay the output out as the
        node's tensor."""
        import jax.numpy as jnp
        n = acc.shape[0]
        y = jnp.clip(acc >> sh, -128, 127).astype(jnp.int32)
        if cp.conv_out is not None:
            cout, oh, ow = cp.conv_out
            return y.transpose(0, 2, 1).reshape(n, cout, oh, ow)
        if cp.vector_in:
            return y[:, 0]
        return y

    def _split(self, node: Node, x):
        import jax.numpy as jnp
        axis = node.attrs.get("axis", -1) % (x.ndim - 1) + 1
        parts = node.attrs["parts"]
        return jnp.split(x, np.cumsum(parts[:-1]), axis=axis)

    def _pool(self, node: Node, x, reduce_max: bool):
        import jax.numpy as jnp
        k = node.attrs.get("kernel", 2)
        pad = node.attrs.get("pad", 0)
        n, c = x.shape[0], x.shape[1]
        if pad:
            fill = -(2 ** 31) if reduce_max else 0
            x = jnp.pad(x, ((0, 0), (0, 0), (pad, pad), (pad, pad)),
                        constant_values=fill)
        win = x.reshape(n, c, -1)[:, :, self._pool_idx[node.name]]
        if reduce_max:
            red = win.max(axis=-1)
        else:
            red = jnp.floor_divide(win.sum(axis=-1), k * k)
        oh, ow = self.graph.shapes[node.outputs[0]][1:]
        return red.reshape(n, c, oh, ow)

    def _dcom(self, node: Node, xs: List, shifts):
        import jax
        import jax.numpy as jnp
        t = node.op_type
        if t == "Relu":
            return jnp.maximum(xs[0], 0)
        if t in ("Add", "Mul"):
            y = xs[0] + xs[1] if t == "Add" else xs[0] * xs[1]
            return jnp.clip(y >> shifts[node.name], -128, 127) \
                .astype(jnp.int32)
        if t == "MaxPool":
            return self._pool(node, xs[0], reduce_max=True)
        if t == "AveragePool":
            return self._pool(node, xs[0], reduce_max=False)
        if t == "GlobalAveragePool":
            hw = xs[0].shape[2] * xs[0].shape[3]
            return jnp.floor_divide(
                xs[0].sum(axis=(2, 3), keepdims=True), hw).astype(jnp.int32)
        if t == "Flatten":
            return xs[0].reshape(xs[0].shape[0], -1)
        if t == "Reshape":
            return xs[0].reshape((xs[0].shape[0],)
                                 + tuple(node.attrs["shape"]))
        if t == "Identity":
            return xs[0]
        if t == "Transpose":
            perm = (0,) + tuple(q + 1 for q in node.attrs["perm"])
            return jnp.transpose(xs[0], perm)
        if t == "Concat":
            axis = node.attrs.get("axis", -1)
            return jnp.concatenate(xs, axis if axis < 0 else axis + 1)
        if t == "MatMul":
            return self._matmul(node, xs, shifts[node.name])
        with jax.named_scope("alu"):
            return self._alu(node, xs[0])

    def _matmul(self, node: Node, xs: List, sh):
        """An activation x activation product, exact: bf16 operand planes
        of int8 range, f32 sums over contraction blocks of
        ``_MATMUL_BLOCK`` (each under 2**24), int32 across blocks.  A
        left operand of Softmax probabilities (up to 2**15) rides two
        planes, its low 7 bits and the rest; the rest sums to at most
        (2**15 + K) / 128 over a row, far inside the range."""
        import jax.numpy as jnp
        a, b = xs
        if node.attrs.get("transpose_b"):
            b = jnp.swapaxes(b, -1, -2)
        planes = [(a & 127, 0), (a >> 7, 7)] if node.name in self._mm_wide \
            else [(a, 0)]
        k = a.shape[-1]
        bf = b.astype(jnp.bfloat16)
        acc = None
        for k0 in range(0, k, _MATMUL_BLOCK):
            k1 = min(k, k0 + _MATMUL_BLOCK)
            for p, lsh in planes:
                part = jnp.matmul(p[..., k0:k1].astype(jnp.bfloat16),
                                  bf[..., k0:k1, :],
                                  preferred_element_type=jnp.float32)
                part = part.astype(jnp.int32) << lsh
                acc = part if acc is None else acc + part
        return jnp.clip(acc >> sh, -128, 127).astype(jnp.int32)

    def _alu(self, node: Node, x):
        """The integer ALU rules of ``cimsim.functional`` on the device."""
        import jax.numpy as jnp
        from jax import lax
        t = node.op_type
        table = self._tables.get(node.name)
        _, fout = fn.alu_fracs(node)
        if t in fn.ELEMENTWISE:
            return _select(table, x + 128)
        if t in ("Softmax", "TopKRouter"):
            causal = bool(node.attrs.get("causal"))
            keep = None
            if causal:
                rows, cols = x.shape[-2:]
                keep = lax.broadcasted_iota(jnp.int32, (rows, cols), 1) <= \
                    lax.broadcasted_iota(jnp.int32, (rows, cols), 0)
            m = (jnp.where(keep, x, -(1 << 20)) if causal else x) \
                .max(axis=-1, keepdims=True)
            d = jnp.clip(m - x, 0, 255)
            e = (_select(table[0], d >> 4) * _select(table[1], d & 15)
                 + (1 << (fn.EXP_FRAC - 1))) >> fn.EXP_FRAC
            if causal:
                e = jnp.where(keep, e, 0)
            if t == "Softmax":
                s = e.sum(axis=-1, keepdims=True)
                return ((e << fout) + (s >> 1)) // s
            s = e.sum(axis=-1, keepdims=True)
            p = ((e << fn.GATE_FRAC) + (s >> 1)) // s
            n_e, k = x.shape[-1], node.attrs["k"]
            key = x * n_e + (n_e - 1 - jnp.arange(n_e, dtype=jnp.int32))
            kth = lax.top_k(key, k)[0][..., k - 1:k]
            g = jnp.where(key >= kth, jnp.maximum(p, 1), 0)
            held = node.attrs.get("held")
            return g if held is None else g[..., np.asarray(held)]
        if t in ("RMSNorm", "LayerNorm"):
            d = x.shape[-1]
            if t == "LayerNorm":
                x = x - (2 * x.sum(axis=-1, keepdims=True) + d) // (2 * d)
            ss = (x * x).sum(axis=-1, keepdims=True)
            m = 16 * (ss // d) + (32 * (ss % d) + d) // (2 * d)
            s = _isqrt(m << 10)
            y = ((x << (fout + 8)) + s) // jnp.maximum(2 * s, 1)
            return jnp.clip(jnp.where(s > 0, y, 0), -128, 127)
        if t == "RoPE":
            cos, sin = table
            half = x.shape[-1] // 2
            shape = (1, x.shape[1]) + (1,) * (x.ndim - 3) + (half,)
            c, s = jnp.asarray(cos).reshape(shape), \
                jnp.asarray(sin).reshape(shape)
            x1, x2 = x[..., :half], x[..., half:]
            rnd = 1 << (fn.ROPE_FRAC - 1)
            y = jnp.concatenate([(x1 * c - x2 * s + rnd) >> fn.ROPE_FRAC,
                                 (x2 * c + x1 * s + rnd) >> fn.ROPE_FRAC],
                                axis=-1)
            return jnp.clip(y, -128, 127)
        raise LoweringError(f"no device rule for {t}")

    # -- routed experts ---------------------------------------------------
    def _lower_alu(self) -> None:
        """Tables of the ALU rules, the MatMuls whose left operand needs
        two planes, and each MoECombine's routed regions."""
        g = self.graph
        self._tables: Dict[str, Any] = {}
        self._mm_wide: set = set()
        for node in g.nodes:
            t = node.op_type
            if t in fn.ELEMENTWISE:
                self._tables[node.name] = fn.elementwise_table(node)
            elif t in ("Softmax", "TopKRouter"):
                self._tables[node.name] = fn.exp_factors(
                    float(node.attrs.get("scale", 1.0)),
                    fn.alu_fracs(node)[0])
            elif t == "RoPE":
                self._tables[node.name] = fn.rope_tables(
                    node, g.shapes[node.inputs[0]][0])
            elif t == "MatMul":
                a, b = (g.producer(x) for x in node.inputs)
                if b is not None and _wide(b):
                    raise LoweringError(f"{node.name}: right operand wider "
                                        "than int8")
                if a is not None and _wide(a):
                    self._mm_wide.add(node.name)
                if g.shapes[node.inputs[0]][-1] > (1 << 17):
                    raise LoweringError(f"{node.name}: accumulation exceeds "
                                        "int32")
        self._combines = [n.name for n in g.nodes if n.op_type == "MoECombine"]
        self._regions: Dict[str, List[List[Node]]] = {}
        self._in_region: set = set()
        for name in self._combines:
            node = g.node(name)
            regions = [self._region(node, j)
                       for j in range(len(node.inputs) - 1)]
            self._regions[name] = regions
            for r in regions:
                self._in_region.update(n.name for n in r)

    def _region(self, combine: Node, j: int) -> List[Node]:
        """The nodes of held expert j's chain: those between a routed Gemm
        of route j and the combine's input j + 1, in graph order."""
        g = self.graph
        anc, stack = set(), [g.producer(combine.inputs[j + 1])]
        while stack:
            n = stack.pop()
            if n is None or n.name in anc:
                continue
            anc.add(n.name)
            stack.extend(g.producer(t) for t in n.inputs)
        region = []
        names: set = set()
        for n in g.nodes:
            if n.name not in anc:
                continue
            routed = n.is_cim and n.attrs.get("route") == j \
                and n.inputs[1:] == combine.inputs[:1]
            fed = any((p := g.producer(t)) is not None and p.name in names
                      for t in (n.inputs[:1] if n.is_cim else n.inputs))
            if routed or fed:
                if not (routed or n.op_type in _ROWWISE):
                    raise LoweringError(f"{n.name}: {n.op_type} inside a "
                                        "routed expert is not row-wise")
                region.append(n)
                names.add(n.name)
        out = g.producer(combine.inputs[j + 1])
        if out is None or out.name not in names:
            raise LoweringError(f"{combine.name}: input {j + 1} is not a "
                                "routed expert's output")
        for n in region:
            for c in g.consumers(n.outputs[0]):
                if c.name not in names and c is not combine:
                    raise LoweringError(f"{n.name} feeds {c.name} outside "
                                        "its routed expert")
        return region

    @staticmethod
    def _route_rows(rows: int, share: float) -> int:
        """``S``: the rows of one routing round of a held expert,
        ``_ROUTE_SLACK`` times its expected share of the dispatch's
        ``rows``, rounded up to 128 (8 below that), at most ``rows``."""
        s = int(np.ceil(_ROUTE_SLACK * np.ceil(rows * share)))
        q = 128 if s >= 128 else 8
        return max(1, min(rows, -(-s // q) * q))

    def _combine(self, node: Node, tensors, packed, shifts, pools):
        """``sum_j gate_j * expert_j(x)`` over the held experts, each
        computed on its routed rows only; returns the requantized output
        and the rows routed to each held expert (the program counter)."""
        import jax
        import jax.numpy as jnp
        from jax import lax
        gates = tensors[node.inputs[0]]
        lead = gates.shape[:-1]
        rows = int(np.prod(lead))
        g = gates.reshape(rows, gates.shape[-1])
        d_out = self.graph.shapes[node.outputs[0]][-1]
        acc = jnp.zeros((rows, d_out), jnp.int32)
        counts = []
        for j, region in enumerate(self._regions[node.name]):
            s = self._route_rows(rows, route_share(region[0]))
            with jax.named_scope("moe_dispatch"):
                sel = g[:, j] > 0
                count = sel.sum(dtype=jnp.int32)
                idx = jnp.nonzero(sel, size=-(-rows // s) * s,
                                  fill_value=0)[0].astype(jnp.int32)

            def body(r, acc, region=region, j=j, idx=idx, count=count, s=s):
                with jax.named_scope("moe_dispatch"):
                    tok = lax.dynamic_slice(idx, (r * s,), (s,))
                    live = r * s + jnp.arange(s, dtype=jnp.int32) < count
                with jax.named_scope("experts"):
                    y = self._run_region(region, node.inputs[j + 1], tok,
                                         tensors, packed, shifts, pools)
                with jax.named_scope("combine"):
                    gj = jnp.where(live, g[tok, j], 0)
                    return acc.at[tok].add(gj[:, None] * y)

            acc = lax.fori_loop(0, (count + s - 1) // s, body, acc)
            counts.append(count)
        with jax.named_scope("combine"):
            y = jnp.clip(acc >> shifts[node.name], -128, 127).astype(jnp.int32)
        return y.reshape(lead + (d_out,)), jnp.stack(counts)

    def _run_region(self, region: List[Node], out: str, tok, tensors,
                    packed, shifts, pools):
        """One round of a held expert's chain on the token rows ``tok``:
        (S, width) rows gathered from the tensors outside the chain."""
        vals: Dict[str, Any] = {}

        def get(t):
            if t not in vals:
                x = tensors[t]
                vals[t] = x.reshape(-1, x.shape[-1])[tok]
            return vals[t]

        for rn in region:
            with _scopes(rn):
                if rn.is_cim:
                    pw = None if self._stream else packed[rn.name]
                    y = self._cim(rn, get(rn.inputs[0])[None], pw,
                                  shifts[rn.name], pools)[0]
                else:
                    y = self._dcom(rn, [get(t) for t in rn.inputs], shifts)
            vals[rn.outputs[0]] = y
        return vals[out]


def _wide(producer: Node) -> bool:
    """Whether a node's output leaves the int8 range: Softmax
    probabilities above 7 fractional bits, or a router's gates."""
    if producer.op_type == "TopKRouter":
        return True
    return producer.op_type == "Softmax" and fn.alu_fracs(producer)[1] > 7


# ---------------------------------------------------------------------------
# Process-wide lowering cache
# ---------------------------------------------------------------------------

_LOWER_CACHE: "OrderedDict[Tuple, LoweredExecutable]" = OrderedDict()
_LOWER_CACHE_MAX = 32


def clear_lower_cache() -> None:
    _LOWER_CACHE.clear()


def _packed_nbytes(obj: Any) -> int:
    """Device-bound bytes in a ``pack`` payload (recursive over the
    dict/list nesting; array leaves expose ``nbytes``)."""
    if hasattr(obj, "nbytes"):
        return int(obj.nbytes)
    if isinstance(obj, dict):
        return sum(_packed_nbytes(v) for v in obj.values())
    if isinstance(obj, (list, tuple)):
        return sum(_packed_nbytes(v) for v in obj)
    return 0


def lower(plan: SchedulePlan, program: Program,
          params: Optional[CimMvmParams] = None, *,
          mode: Optional[str] = None, stream="auto",
          use_kernel: Optional[bool] = None,
          interpret: Optional[bool] = None,
          faults=None,
          cache: bool = True) -> LoweredExecutable:
    """Lower a compiled ``(plan, program)`` to a batched executable.

    The MVM execution route is a backend-registry decision (force with
    ``mode=``; the deprecated ``use_kernel=``/``interpret=`` booleans
    keep their historical meaning); ``stream="auto"`` enables
    weight-update streaming exactly for multi-segment schedules.
    ``faults`` (a ``cimsim.faults.FaultMap``) folds device faults into
    weight packing plus trace-constant post-MVM offsets.

    Cached process-wide by ``compile_key_for_plan(plan) x params x
    resolved route x streaming x fault-map identity``, so repeated
    lowerings of the same compile config — calibration loops,
    verification sweeps, serving restarts — reuse the traced executable
    and its jit cache.
    """
    from ..core import compiler
    params = params or cim_mvm_params(plan.arch)
    route = _resolve_executor_route(None, mode, use_kernel, interpret)
    streamed = (max(1, len(plan.segments)) > 1) if stream == "auto" \
        else bool(stream)
    key = None
    if cache:
        key = (compiler.compile_key_for_plan(plan), params, route.mode,
               streamed, None if faults is None else faults.token)
        hit = _LOWER_CACHE.get(key)
        if hit is not None:
            _LOWER_CACHE.move_to_end(key)
            obs_metrics.count("executor_lower_cache_hits_total")
            return hit
    name = plan.graph.name
    t0 = time.perf_counter()
    with obs_trace.span("cim.executor.lower", obs_trace.EXECUTOR_TRACK,
                        name, event=f"lower:{name}", cat="executor",
                        route=route.mode, segments=len(plan.segments),
                        streamed=streamed):
        exe = LoweredExecutable(plan, program, params, route=route,
                                stream=streamed, faults=faults)
    obs_metrics.count("executor_lowerings_total")
    obs_metrics.observe("executor_lower_s", time.perf_counter() - t0)
    if obs_trace.get_trace() is not None:
        # remember the compile key so the first dispatch can close the
        # compile→dispatch flow arrow (ids match compile_graph's start)
        exe._flow_key = (key[0] if key is not None
                         else compiler.compile_key_for_plan(plan))
    if key is not None:
        _LOWER_CACHE[key] = exe
        while len(_LOWER_CACHE) > _LOWER_CACHE_MAX:
            _LOWER_CACHE.popitem(last=False)
    return exe

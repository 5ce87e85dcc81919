"""Batched CIM inference service over a trace-lowered executor.

The serving-side consumer of cimsim.executor: compile a workload for a
CIM chip once, lower the meta-operator flow once, then serve request
traffic by stacking queued inputs on the executor's batch axis — one
device dispatch per batch instead of one interpreter walk per request.
``use_executor=False`` serves through the op-by-op interpreter
instead (same outputs, orders of magnitude slower), which is also how
the service is tested.  A program the executor cannot lower raises
(``LoweringError``, ``KernelUnsupportedError``): the service never
falls back to the interpreter on its own.

Request/stats shapes live in ``serving.common`` (shared with the LM
batch server and the multi-tenant fleet); ``serve_padded`` is the
fleet batcher's entry point — it pads a partial batch up to a bucket
size so the bucket's already-traced executable is reused instead of
tracing a new batch shape per ragged queue drain.

Units and clocks: ``dispatch``/``serve_padded`` return **wall-clock
seconds** (``time.time()`` around the device call); the compiled plan's
latency/energy estimates are **compiler cycles/pJ** and never mix into
serve times.  Thread-safety: the jitted executable is safe to share,
but ``stats`` and the warm-shape set are plain mutable state — one
service instance per serving thread.
"""
from __future__ import annotations

import time
from typing import Dict, List, Optional

import numpy as np

from ..core import compiler
from ..core.abstraction import CIMArch
from ..core.graph import Graph
from ..kernels.cim_mvm import CimMvmParams, cim_mvm_params
from ..obs import metrics as obs_metrics
from ..obs import trace as obs_trace
from .common import CimRequest, ServiceStats  # noqa: F401  (re-export)


class CimBatchService:
    """Fixed-workload inference service with batched execution.

    Weights default to the deterministic test weights and shifts to one
    reference calibration pass (the §4.1 verification setup); production
    embedders can pass their own ``weights``/``shifts``.

    ``cache`` (a ``dse.CompileCache``) warm-loads the compiled plan from
    disk instead of recompiling — the fleet engine pool hands every
    tenant the campaign cache here.  ``compile_kwargs`` carries compiler
    knob overrides (binding / use_pipeline / use_duplication, e.g. a DSE
    best point's ``compile_kwargs()``); ``level`` stays a convenience
    alias for the common single-knob case.
    """

    def __init__(self, graph: Graph, arch: CIMArch, *, level=None,
                 seed: int = 0, max_batch: int = 8,
                 params: Optional[CimMvmParams] = None,
                 weights: Optional[Dict[str, np.ndarray]] = None,
                 shifts: Optional[Dict[str, int]] = None,
                 use_executor: bool = True,
                 cache=None,
                 compile_kwargs: Optional[Dict] = None):
        from ..cimsim.functional import (calibrate_shifts, make_input,
                                         make_weights)
        self.graph = graph
        self.arch = arch
        self.max_batch = max_batch
        self.use_executor = use_executor
        self.params = params or cim_mvm_params(arch)
        self.weights = weights if weights is not None \
            else make_weights(graph, seed)
        if shifts is None:
            # one reference forward pass on the host: timed, since at
            # published input sizes it is a large share of set-up
            t0 = time.perf_counter()
            with obs_trace.span("cim.service.calibrate",
                                obs_trace.SERVING_TRACK, graph.name):
                shifts = calibrate_shifts(graph, self.weights,
                                          make_input(graph, seed),
                                          self.params)
            obs_metrics.observe("service_calibrate_s",
                                time.perf_counter() - t0)
        self.shifts = shifts
        self.stats = ServiceStats()
        self._warmed: set = set()        # batch sizes already jit-traced
        kwargs = dict(compile_kwargs or {})
        kwargs.setdefault("level", level)
        if use_executor:
            from ..cimsim.executor import lower
            res = compiler.compile_graph(graph, arch, cache=cache, **kwargs)
            self._exe = lower(res.plan, res.program, params=self.params)
            self._packed = self._exe.pack(self.weights)
        else:
            from ..cimsim.functional import FunctionalSimulator
            res = compiler.compile_graph(graph, arch, cache=cache,
                                         expand=True, **kwargs)
            self._sim = FunctionalSimulator(res.plan, res.program,
                                            self.weights, self.shifts,
                                            params=self.params)

    @property
    def executor_stats(self):
        """The lowered executable's ``ExecutorStats`` (segments, streamed
        weight updates, resolved kernel route), or ``None`` when the
        service runs the op-by-op interpreter (``use_executor=False``)."""
        return self._exe.stats if self.use_executor else None

    def serve(self, requests: List[CimRequest]) -> List[CimRequest]:
        """Serve ``requests`` in arrival order, ``max_batch`` at a time.

        Each batch is one executor dispatch (ragged final batches just
        trace a second batch shape, cached thereafter).  The first
        dispatch of a new batch shape runs once untimed to warm the jit
        cache, so ``latency_s`` / ``ServiceStats`` report steady-state
        serving cost rather than trace time.
        """
        done: List[CimRequest] = []
        for i in range(0, len(requests), self.max_batch):
            batch = requests[i:i + self.max_batch]
            dt = self.dispatch(batch)
            for r in batch:
                r.latency_s = dt
            self.stats.record([dt] * len(batch), dt)
            done.extend(batch)
        return done

    def serve_padded(self, batch: List[CimRequest],
                     bucket: Optional[int] = None) -> float:
        """One bucket-shaped dispatch for ``len(batch) <= bucket``
        requests; returns the wall time.  The fleet batcher's entry
        point: padding to a bucket reuses that bucket's cached
        executable instead of tracing every ragged batch size.  Fills
        ``outputs`` but leaves latency/stats accounting to the caller
        (the fleet adds queue wait before recording)."""
        return self.dispatch(batch, pad_to=bucket)

    def dispatch(self, batch: List[CimRequest],
                 pad_to: Optional[int] = None) -> float:
        """Serve one batch (warm-once per shape), return the wall time."""
        if not batch:
            return 0.0
        shape = pad_to if (pad_to and self.use_executor) else len(batch)
        if self.use_executor and shape not in self._warmed:
            self._serve_batch(batch, pad_to=pad_to)
            self._warmed.add(shape)
        t0 = time.time()
        self._serve_batch(batch, pad_to=pad_to)
        return time.time() - t0

    def _serve_batch(self, batch: List[CimRequest],
                     pad_to: Optional[int] = None) -> None:
        if not self.use_executor:
            for r in batch:
                out = self._sim.run({k: np.asarray(v)
                                     for k, v in r.inputs.items()})
                r.outputs = {t: np.asarray(out[t]) for t in self.graph.outputs}
            return
        n = len(batch)
        pad = max(0, (pad_to or n) - n)
        stacked = {}
        with obs_trace.span("cim.service.stack", obs_trace.SERVING_TRACK,
                            self.graph.name):
            for name in self.graph.inputs:
                rows = [np.asarray(r.inputs[name]) for r in batch]
                rows += [rows[-1]] * pad  # pad-to-bucket: repeat last row
                stacked[name] = np.stack(rows)
        outs = self._exe.run_batch(stacked, packed=self._packed,
                                   shifts=self.shifts)
        for i, r in enumerate(batch):
            r.outputs = {t: outs[t][i] for t in self.graph.outputs}

"""Backend-capability registry: which kernel runs how, on what.

The CIM-MLC premise is that the compiler must know the hardware it
targets.  This module is that knowledge for the *host* side of the
stack: every CIM kernel has up to three execution routes —

  * ``compiled``  — a genuinely compiled ``pallas_call`` (TPU/GPU; the
                    fast path on accelerators),
  * ``interpret`` — the same Pallas kernel body run by the Pallas
                    interpreter (any platform; the CPU validation path
                    that exercises the kernel's exact block/grid logic),
  * ``xla``       — the pure-jnp oracle (``ref.cim_mvm_ref``) compiled
                    by XLA (any platform; the fast CPU path and the
                    semantic ground truth).

Callers no longer thread ``interpret=``/``use_kernel=`` booleans
through every layer; they ask the registry for a :class:`KernelRoute`
(``resolve``) and the registry decides from the active JAX platform and
per-kernel capability.  Overrides exist at three levels:

  * per-call: ``cim_mvm(..., mode="interpret")``,
  * process-scoped: ``with backend.override("interpret"): ...`` (tests,
    conformance sweeps),
  * environment: ``REPRO_KERNEL_MODE=interpret|xla|compiled|auto``
    (the CI conformance legs run the same suite under each mode).

Asking for an unsupported combination (``compiled`` on CPU) raises
``KernelUnsupportedError``, which reaches the caller of the executor and
of the serving stack unchanged: no route falls back to another.
"""
from __future__ import annotations

import contextlib
import dataclasses
import os
import pathlib
from typing import Dict, Optional, Tuple

#: execution routes, in "fast on an accelerator" order
MODES = ("compiled", "interpret", "xla")
AUTO = "auto"

_ENV_MODE = "REPRO_KERNEL_MODE"
_ENV_PLATFORM = "REPRO_KERNEL_PLATFORM"
_ENV_JAX_CACHE = "JAX_COMPILATION_CACHE_DIR"
_DEFAULT_JAX_CACHE = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


class KernelUnsupportedError(RuntimeError):
    """The requested (kernel, mode, platform) combination cannot run."""


@dataclasses.dataclass(frozen=True)
class KernelCapability:
    """Per-kernel support matrix.

    ``compiled_platforms`` lists JAX platforms whose backend can lower
    the kernel's ``pallas_call`` for real; ``interpret`` and ``xla``
    routes are platform-independent (the Pallas interpreter and the jnp
    oracle run anywhere jax does).
    """

    name: str
    compiled_platforms: Tuple[str, ...] = ("tpu", "gpu")
    has_interpret: bool = True
    has_xla: bool = True
    note: str = ""

    def modes_on(self, platform: str) -> Tuple[str, ...]:
        out = []
        if platform in self.compiled_platforms:
            out.append("compiled")
        if self.has_interpret:
            out.append("interpret")
        if self.has_xla:
            out.append("xla")
        return tuple(out)


#: the registry proper — one entry per public kernel entry point
REGISTRY: Dict[str, KernelCapability] = {
    "cim_mvm": KernelCapability(
        "cim_mvm",
        note="bit-sliced crossbar MVM; Pallas kernel is MXU-batched "
             "over parallel-row groups"),
    "cim_mvm_tiles": KernelCapability(
        "cim_mvm_tiles",
        note="tile-batched MVM (executor fast path); Pallas route adds "
             "the tile axis as the leading grid dimension"),
    "cim_mvm_signed": KernelCapability(
        "cim_mvm_signed",
        note="offset-encoded signed MVM; routes through cim_mvm"),
}


@dataclasses.dataclass(frozen=True)
class KernelRoute:
    """One resolved routing decision: *this* kernel runs *this* way."""

    kernel: str
    platform: str
    mode: str            # "compiled" | "interpret" | "xla"
    reason: str = ""

    #: legacy boolean views (the pre-registry calling convention)
    @property
    def use_kernel(self) -> bool:
        return self.mode != "xla"

    @property
    def interpret(self) -> bool:
        return self.mode == "interpret"


# -- platform detection ------------------------------------------------------

def detect_platform() -> str:
    """The active JAX platform (``cpu``/``gpu``/``tpu``).

    ``REPRO_KERNEL_PLATFORM`` overrides detection (useful to exercise
    routing decisions for a platform the test host does not have —
    resolution only; actually *running* a compiled route still needs
    the hardware).
    """
    env = os.environ.get(_ENV_PLATFORM)
    if env:
        return env
    import jax
    return jax.default_backend()


# -- overrides ---------------------------------------------------------------

#: process-scoped mode overrides: kernel name -> mode ("" key = all kernels)
_OVERRIDES: Dict[str, str] = {}


def set_override(mode: Optional[str], kernel: str = "") -> None:
    """Set (or with ``None`` clear) a process-scoped mode override.

    ``kernel=""`` applies to every kernel; a named override wins over
    the blanket one.  Overrides beat the environment variable, which
    beats auto-resolution.
    """
    if mode is None:
        _OVERRIDES.pop(kernel, None)
    else:
        _check_mode(mode)
        _OVERRIDES[kernel] = mode


@contextlib.contextmanager
def override(mode: str, kernel: str = ""):
    """``with backend.override("interpret"): ...`` — scoped route forcing."""
    prev = _OVERRIDES.get(kernel)
    set_override(mode, kernel)
    try:
        yield
    finally:
        set_override(prev, kernel)


def _check_mode(mode: str) -> None:
    if mode not in MODES and mode != AUTO:
        raise ValueError(f"unknown kernel mode {mode!r}; "
                         f"expected one of {MODES + (AUTO,)}")


def _requested_mode(kernel: str, mode: Optional[str]) -> str:
    """Resolution order: per-call > per-kernel override > blanket
    override > environment > auto."""
    if mode:
        _check_mode(mode)
        return mode
    for key in (kernel, ""):
        if key in _OVERRIDES:
            return _OVERRIDES[key]
    env = os.environ.get(_ENV_MODE, "").strip().lower()
    if env:
        _check_mode(env)
        return env
    return AUTO


# -- resolution --------------------------------------------------------------

def supports(kernel: str, mode: str, platform: Optional[str] = None) -> bool:
    """True if ``kernel`` can execute via ``mode`` on ``platform``."""
    cap = REGISTRY[kernel]
    return mode in cap.modes_on(platform or detect_platform())


def resolve(kernel: str, mode: Optional[str] = None,
            platform: Optional[str] = None) -> KernelRoute:
    """Decide how ``kernel`` should execute right now.

    Auto policy: compiled where the platform supports it (TPU/GPU);
    the XLA-compiled oracle elsewhere (CPU) — the Pallas interpreter is
    never chosen automatically, it is the explicit validation route.
    Raises :class:`KernelUnsupportedError` if a forced mode cannot run.
    """
    if kernel not in REGISTRY:
        raise KeyError(f"unknown kernel {kernel!r}; "
                       f"registered: {sorted(REGISTRY)}")
    platform = platform or detect_platform()
    want = _requested_mode(kernel, mode)
    avail = REGISTRY[kernel].modes_on(platform)
    if want == AUTO:
        if "compiled" in avail:
            return KernelRoute(kernel, platform, "compiled",
                               f"auto: {platform} compiles pallas_call")
        return KernelRoute(kernel, platform, "xla",
                           f"auto: {platform} has no compiled route, "
                           "taking the XLA-compiled oracle")
    if want not in avail:
        raise KernelUnsupportedError(
            f"{kernel}: mode {want!r} is not supported on {platform!r} "
            f"(available: {avail})")
    return KernelRoute(kernel, platform, want, "explicitly requested")


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    the cache stays there.  Otherwise it goes to the fixed
    ``<checkout>/.jax_cache``: the directory is part of the cache key, so
    it must not move between runs.  This caches compiled XLA programs; the
    CIM plan cache (``REPRO_COMPILE_CACHE_DIR``, ``dse.cache``) is another
    thing.
    """
    import jax
    path = os.environ.get(_ENV_JAX_CACHE)
    if not path:
        path = str(_DEFAULT_JAX_CACHE)
        jax.config.update("jax_compilation_cache_dir", path)
    return path


def capability_matrix(platform: Optional[str] = None) -> Dict[str, Dict]:
    """Docs/bench view: per kernel, the supported modes and the route
    auto-resolution would pick on ``platform`` (default: detected)."""
    platform = platform or detect_platform()
    out: Dict[str, Dict] = {}
    for name, cap in REGISTRY.items():
        route = resolve(name, mode=AUTO, platform=platform)
        out[name] = {
            "platforms": {p: cap.modes_on(p) for p in ("cpu", "gpu", "tpu")},
            "auto_mode": route.mode,
            "note": cap.note,
        }
    return out

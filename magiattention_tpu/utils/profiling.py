"""Tracing / profiling helpers (ref: magi_attention/utils/nvtx.py).

The reference instruments every hot-path function with NVTX ranges and opens
torch.profiler windows; the TPU equivalents are ``jax.named_scope`` (shows up
in XLA HLO + xprof traces) and ``jax.profiler`` trace windows.

What a scope does to a device trace. A v5e trace knows an operation by its
HLO instruction's NAME; the scopes' full path is in the instruction's
``op_name`` metadata only, which the trace does not carry. XLA names a
``tpu_custom_call`` (a Pallas kernel) after the INNERMOST named scope of its
``op_name`` and nothing else after a scope at all: fusions are named by
what they fuse, and a collective the library issues keeps its JAX
primitive's name where XLA has an instruction of that kind
(``ragged_all_to_all``, ``all_to_all``; a ``ppermute`` becomes
``collective-permute-start``). So:

* the scopes here are gated on ``MAGI_ATTENTION_PROFILE_MODE`` as the
  reference gates its NVTX ranges (which cost at run time), and they show
  in ``op_name`` / ``compiled.as_text()``, not as names in a trace;
* a kernel's identity is not gated: ``kernels/_named.py`` binds every
  Pallas call under ``magi<body>`` (``magi_fwd_kernel``,
  ``magi_bwd_dq_kernel``, ...), always, and that innermost scope is what a
  trace shows. Inside ``_multi_ffa*`` the instruction is therefore
  ``magi_*`` and ``ffa_fwd_stage{i}`` survives in ``op_name`` only;
* ``ffa_fwd_stage{i}`` / ``ffa_bwd_delta`` / ``ffa_bwd`` / ``lse_merge``
  live in ``_multi_ffa*`` and are entered on the multi-stage overlap path
  only; the merged single-call path (``use_overlap`` off, one stage) goes
  straight to ``ffa_attn_with_plan``. ``group_cast_stage{i}`` is entered on
  both, and shows in ``op_name`` only.
"""

from __future__ import annotations

import functools
from contextlib import contextmanager
from typing import Callable

import jax

from ..env import general as env_general


def instrument_scope(fn: Callable | None = None, *, name: str | None = None):
    """Decorator wrapping a function in a ``jax.named_scope`` (the
    ``instrument_nvtx`` equivalent, ref nvtx.py:81). Scope names appear in
    HLO metadata and profiler traces.

    Gated on ``MAGI_ATTENTION_PROFILE_MODE`` (read per call, i.e. per
    trace): off by default, zero overhead in production programs — the
    reference gates its nvtx instrumentation the same way
    (env/general.py:191)."""

    def wrap(f):
        scope = name or f.__qualname__

        @functools.wraps(f)
        def inner(*args, **kwargs):
            if not env_general.is_profile_mode_enable():
                return f(*args, **kwargs)
            with jax.named_scope(scope):
                return f(*args, **kwargs)

        return inner

    return wrap(fn) if fn is not None else wrap


@contextmanager
def profile_scope(name: str):
    """Inline ``jax.named_scope`` gated on MAGI_ATTENTION_PROFILE_MODE —
    for loop bodies (per-stage kernels / casts) where a decorator can't
    reach."""
    if not env_general.is_profile_mode_enable():
        yield
    else:
        with jax.named_scope(name):
            yield


def instrument_host(fn: Callable | None = None, *, name: str | None = None):
    """Host-side profiler annotation (``jax.profiler.TraceAnnotation``) for
    UN-traced hot paths — solvers, plan builders, runtime init. These run in
    Python, so named_scope (an HLO-metadata construct) cannot see them; the
    TraceAnnotation puts them on the profiler timeline instead (the ref
    add_nvtx_event analogue). Gated on MAGI_ATTENTION_PROFILE_MODE."""

    def wrap(f):
        scope = name or f.__qualname__

        @functools.wraps(f)
        def inner(*args, **kwargs):
            if not env_general.is_profile_mode_enable():
                return f(*args, **kwargs)
            with jax.profiler.TraceAnnotation(scope):
                return f(*args, **kwargs)

        return inner

    return wrap(fn) if fn is not None else wrap


class switch_profile:
    """Start/stop a jax profiler window (ref nvtx.py:110 switch_profile).

    Usable explicitly or as a context manager (exception-safe: the trace
    window is closed even when the body raises)::

        prof = switch_profile(log_dir="/tmp/trace")
        prof.start(); ...steps...; prof.stop()

        with switch_profile(log_dir="/tmp/trace"):
            ...steps...
    """

    def __init__(self, log_dir: str = "/tmp/magiattention_tpu_trace") -> None:
        self.log_dir = log_dir
        self._running = False

    def start(self) -> None:
        if not self._running:
            jax.profiler.start_trace(self.log_dir)
            self._running = True

    def stop(self) -> None:
        if self._running:
            jax.profiler.stop_trace()
            self._running = False

    def __enter__(self) -> "switch_profile":
        self.start()
        return self

    def __exit__(self, *exc_info) -> None:
        self.stop()

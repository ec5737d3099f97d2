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
  in ``op_name`` / ``compiled.as_text()``, not as names in a trace. The
  compiled program is what joins the two: :func:`instruction_scopes` reads
  its text into ``{instruction name: (scopes, pass)}``, and a trace's
  events are looked up in that table by their names
  (docs/observability.md, "Reading a device trace by region");
* a step built by ``models/llama.py:_StepJit`` hands out its own compiled
  text: under the flag it remembers the abstract signature of its last
  call, and :func:`compiled_step_texts` lowers and compiles from it after
  the steps (the jit's own executable in the process that ran it, 0.2-1.3 s
  on the chip; elsewhere a load from the persistent cache);
* the model's regions are spelled once, in :data:`MODEL_REGIONS`; the model
  files open them with :func:`profile_scope` and the benchmark's reader
  (``cellbench/regions.py``) imports the same tuple;
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
import re
from contextlib import contextmanager
from types import SimpleNamespace
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


# ---------------------------------------------------------------------------
# the model's regions, and the join from a compiled step to a device trace
# ---------------------------------------------------------------------------

# The one place that spells the regions the model files open (llama.py,
# hybrid.py, moe.py: ``with profile_scope(<name>)`` round code that is there)
# and the benchmark's reader sums (cellbench/regions.py). PERF.md §3 says
# what each encloses. None holds ``magi_``, ``ragged_dot`` or a collective's
# name: the benchmark matches those anywhere in an instruction's name.
MODEL_REGIONS = (
    "embed", "attn_qkv", "attn_out", "mlp", "ssm", "moe_route", "moe_rows",
    "moe_experts", "moe_shared", "head_loss", "update",
)
# Regions INSIDE one of those, opened only where a family's leaves bring the
# work (a step without such leaves names none of them): ``mla_assemble``, in
# ``attn_qkv``, is what a latent-attention block does between its projections
# and ``calc_attn`` — the rotation of the rotary channels, the one rotary key
# laid out for every head, the concatenations into q and k, q's scales.
# :func:`region_of` gives an instruction to the innermost, so a sum over
# ``attn_qkv`` holds the projections and not this.
NESTED_REGIONS = ("mla_assemble",)
# ``with profile_scope(REGION.mlp)``: the names as attributes, so that a model
# file spells none and a misspelt one fails where it is written
REGION = SimpleNamespace(
    **{name: name for name in MODEL_REGIONS + NESTED_REGIONS})
# the span DistAttnRuntime.calc_attn has always had: between attn_qkv and
# attn_out, the kernels and everything round them
ATTN_REGION = "DistAttnRuntime.calc_attn"
_REGIONS = frozenset((*MODEL_REGIONS, *NESTED_REGIONS, ATTN_REGION))
PASSES = ("fwd", "refwd", "bwd", "none")
# what jax.checkpoint's re-run of the forward puts in the path (JAX 0.9:
# ``transpose(jvp(..))/checkpoint/rematted_computation/<scopes>/<primitive>``;
# the backward proper is under ``checkpoint`` without it)
REMAT_MARKER = "rematted_computation"

# the steps that ran under the flag, by name: models/llama.py:_StepJit puts
# itself here with its last call
_STEPS_SEEN: dict = {}


def region_of(scopes) -> str | None:
    """The innermost element of ``scopes`` that is one of
    :data:`MODEL_REGIONS`, :data:`NESTED_REGIONS` or :data:`ATTN_REGION`;
    ``None`` outside all. A path XLA cut short that still holds a
    ``group_cast*`` / ``group_reduce*`` span is :data:`ATTN_REGION`'s: only
    the runtime under it opens those."""
    for scope in reversed(scopes or ()):
        if scope in _REGIONS:
            return scope
    if any(s.startswith(("group_cast", "group_reduce")) for s in scopes or ()):
        return ATTN_REGION
    return None


def abstract_signature(args, kwargs):
    """``(args, kwargs)`` with every array replaced by its
    ``jax.ShapeDtypeStruct`` (the sharding of a committed ``jax.Array``
    kept) and everything else, the static arguments, as it is: what
    ``lower`` needs to make the same program again."""

    def abstract(x):
        if not (hasattr(x, "shape") and hasattr(x, "dtype")):
            return x
        sharding = x.sharding if getattr(x, "committed", False) else None
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding)

    return jax.tree.map(abstract, (args, kwargs))


def note_step_call(step, args, kwargs) -> None:
    """Under the flag, keep ``step`` (a ``_StepJit``) and the arguments of
    this call for :func:`compiled_step_texts`: two assignments, no lowering
    and nothing computed (the arrays are donated or small, and what is read
    of them later, shape, dtype and sharding, outlives their buffers).
    Nothing with the flag off."""
    if env_general.is_profile_mode_enable():
        step.last_call = (args, kwargs)
        _STEPS_SEEN[step.name] = step


def compiled_step_texts() -> dict[str, str]:
    """``{step name: optimized HLO text}`` of every ``_StepJit`` that ran
    under the flag, lowered from the signature of its last call and
    compiled: the executable that just ran, from the jit's own cache or the
    persistent one. Call it after the steps, never inside a timed window.
    ``{}`` with the flag off."""
    if not env_general.is_profile_mode_enable():
        return {}
    texts = {}
    for name, step in _STEPS_SEEN.items():
        args, kwargs = step.signature
        texts[name] = step.lower(*args, **kwargs).compile().as_text()
    return texts


_COMPUTATION = re.compile(r"^(?:ENTRY )?%?(\S+) \(.*\{\s*$")
_INSTRUCTION = re.compile(r"^\s+(ROOT )?%?(\S+) = (.*)$")
_OP_NAME = re.compile(r'op_name="((?:[^"\\]|\\.)*)"')
_CALLS = re.compile(r"\bcalls=%?([^\s,}]+)")
_WRAPPED = re.compile(r"(\w+)\((.*)\)")
_MATMULS = ("convolution", "dot")


def _opcode(rest: str) -> str:
    """The opcode of an instruction's text after ``<name> = ``: what stands
    between the result type (a tuple's may hold spaces) and ``(``."""
    if rest.startswith("("):
        depth = 0
        for i, ch in enumerate(rest):
            depth += (ch == "(") - (ch == ")")
            if depth == 0:
                break
        rest = rest[i + 1:].lstrip()
    else:
        rest = rest.partition(" ")[2]
    return rest.partition("(")[0]


def _split_path(path: str) -> list[str]:
    """``path`` cut at the ``/`` that no parenthesis encloses."""
    parts, depth, start = [], 0, 0
    for i, ch in enumerate(path):
        depth += (ch == "(") - (ch == ")")
        if ch == "/" and depth == 0:
            parts.append(path[start:i])
            start = i + 1
    parts.append(path[start:])
    return parts


def scope_path(op_name: str) -> tuple[tuple[str, ...], str]:
    """``(scopes, pass)`` of one ``op_name``. JAX prints a transform round
    the path element that follows it (``jvp(mlp)/dot_general``,
    ``transpose(jvp())/mul``), a jitted function as ``jit(<name>)``, and the
    primitive last: the decorations are taken off, ``jit(..)`` and the
    primitive left out, and the pass is ``refwd`` where the path crosses
    :data:`REMAT_MARKER`, else ``bwd`` under a ``transpose``, ``fwd`` under
    a ``jvp`` alone, ``none`` under no transform (the SGD update, what XLA
    added)."""
    scopes: list[str] = []
    transforms: set[str] = set()

    def walk(parts, primitive_last: bool):
        for n, part in enumerate(parts):
            m = _WRAPPED.fullmatch(part)
            if m is None:
                if not (primitive_last and n == len(parts) - 1):
                    scopes.append(part)
            elif m[1] != "jit":
                transforms.add(m[1])
                if m[2]:
                    walk(_split_path(m[2]), False)

    walk(_split_path(op_name), True)
    if REMAT_MARKER in scopes:
        which = "refwd"
    elif "transpose" in transforms:
        which = "bwd"
    elif "jvp" in transforms:
        which = "fwd"
    else:
        which = "none"
    return tuple(scopes), which


def instruction_scopes(text: str):
    """From a compiled program's text (``compiled.as_text()``) to what a
    device trace lacks: ``(table, on_boundary)``.

    ``table`` is ``{instruction name: (scopes, pass)}`` for every
    instruction of every computation of the module, fused ones too, the
    name as a trace shows it (no ``%``). ``scopes`` is the scope path of the
    instruction's ``op_name``, outermost first (:func:`scope_path`; the
    names JAX's control flow and ``checkpoint`` add stay in it), ``pass``
    one of :data:`PASSES`. An instruction without an ``op_name`` (a layout
    ``copy``, a collective the partitioner inserted) has ``(None, "none")``.

    XLA cuts the head off some paths (a gather it expands is left with
    ``cond/branch_1_fun/moe_rows/gather``): such a path still names its
    region but not its pass, and the instruction takes the pass most of its
    computation's whole paths (those from ``jit(..)`` down) have, where the
    computation is not the entry and has any.

    A **fusion** takes the entry of the ``convolution`` / ``dot`` it holds
    where those it holds name exactly one region (:func:`region_of`): a
    weight-gradient matmul with the SGD update as its epilogue is the
    matmul's region and pass, where its time goes. Otherwise it takes its
    own or its root's, whichever names a region (a whole path first); a
    fusion whose root is a bare ``tuple`` (several outputs) and one XLA left
    without metadata takes the entry most of its instructions carry; last,
    whatever ``op_name`` itself or its root has. ``on_boundary`` is
    ``{fusion name: whether its instructions name more than one region}``,
    so a reader can say how much time sits on a boundary."""
    own: dict[str, tuple] = {}  # name -> (scopes, pass, path is whole)
    inside: dict[str, list[tuple[str, str, bool]]] = {}  # computation -> rows
    fusions: dict[str, str] = {}  # fusion instruction -> its computation
    rows, entry = None, None
    for line in text.splitlines():
        m = _INSTRUCTION.match(line)
        if m is None:
            c = _COMPUTATION.match(line)
            if c is not None:
                rows = inside.setdefault(c[1], [])
                if line.startswith("ENTRY"):
                    entry = c[1]
            continue
        root, name, rest = bool(m[1]), m[2], m[3]
        opcode = _opcode(rest)
        named = _OP_NAME.search(rest)
        own[name] = (*scope_path(named[1]), named[1].startswith("jit(")) if (
            named) else (None, "none", False)
        if rows is not None:
            rows.append((name, opcode, root))
        if opcode == "fusion":
            called = _CALLS.search(rest)
            if called:
                fusions[name] = called[1]
    for computation, members in inside.items():
        whole = [own[i][1] for i, _, _ in members if own[i][2]]
        if computation == entry or not whole:
            continue
        most = max(PASSES, key=whole.count)
        for i, _, _ in members:
            scopes, _, is_whole = own[i]
            if scopes is not None and not is_whole:
                own[i] = (scopes, most, False)
    table = {name: entry[:2] for name, entry in own.items()}
    on_boundary = {}
    for name, computation in fusions.items():
        members = inside.get(computation, [])
        regional = [own[i] for i, _, _ in members if region_of(own[i][0])]
        on_boundary[name] = len({region_of(e[0]) for e in regional}) > 1
        matmuls = [own[i] for i, opcode, _ in members
                   if opcode in _MATMULS and region_of(own[i][0])]
        # itself and its root, a whole path before one cut short
        near = sorted(
            [own[name]] + [own[i] for i, _, root in members if root],
            key=lambda e: not e[2])
        if len({region_of(e[0]) for e in matmuls}) == 1:
            pick = matmuls[0]
        elif any(region_of(e[0]) for e in near):
            pick = next(e for e in near if region_of(e[0]))
        elif regional:
            pick = max(regional, key=regional.count)
        else:
            pick = next((e for e in near if e[0] is not None), own[name])
        table[name] = pick[:2]
    return table, on_boundary

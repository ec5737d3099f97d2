"""``pallas_call`` bound under the kernel body's own name, always.

XLA names a ``tpu_custom_call`` instruction after the INNERMOST named scope
of its ``op_name``; with none of ours around it that is whatever transform
or caller happens to be innermost (``jvp(DistAttnRuntime.calc_attn)`` on one
chip, ``shard_map`` on several), the same for every kernel body. So every
``pallas_call`` of the package is bound under ``jax.named_scope("magi" +
body.__name__)``: a device trace then shows ``%magi_fwd_kernel.1``,
``%magi_delta_kernel.1``, ``%magi_bwd_dq_kernel.1``, ... forward and
backward, inside ``shard_map`` too. Scopes further out (``ffa_fwd_stage{i}``
of the multi-stage path, ``DistAttnRuntime.calc_attn``) survive in
``op_name`` only.

* Not gated on ``MAGI_ATTENTION_PROFILE_MODE``: a named scope is a string in
  the HLO's metadata and costs nothing when the program runs, and a kernel's
  identity belongs in a production trace as well.
* No ``name=`` argument to ``pl.pallas_call``: it opens the same scope but
  also overwrites the body's ``debug_info.func_name``, which is what the
  benchmark's and ``chip_smoke.py``'s kernel reports read. The body keeps
  its Python name; the trace's name differs from it by the prefix alone.
* The scope is held while the call is BOUND (around the call of what
  ``pl.pallas_call`` returns), not while it is built, and the operands are
  evaluated before it: nothing else is traced under a kernel's name.
* The prefix tells the library's kernels from any other Pallas kernel of a
  user's model, and keeps every name clear of a collective primitive's
  (``all_to_all``, ``ppermute``, ``psum``, ...).
* A call made for a labelled runtime key (``DistAttnRuntimeKey.label``, e.g.
  ``window`` and ``full`` where a model attends under two masks a step)
  carries the label after the body's name, ``magi_fwd_kernel_window``: the
  body's name is still there for whoever looks for it, and a trace tells
  one key's calls from another's. Without a label the name is the body's.
"""

from __future__ import annotations

from functools import partial

import jax
from jax.experimental import pallas as pl

KERNEL_SCOPE_PREFIX = "magi"


def kernel_scope_name(kernel) -> str:
    """``"magi" + body.__name__``, the body taken from the kernel function
    itself (``functools.partial`` unwrapped)."""
    while isinstance(kernel, partial):
        kernel = kernel.func
    return KERNEL_SCOPE_PREFIX + kernel.__name__


def pallas_call(kernel, label: str | None = None, **kwargs):
    """``pl.pallas_call(kernel, **kwargs)``, bound under the kernel's name
    (and ``_<label>`` after it, where the caller has one).
    ``pl.pallas_call`` is looked up when called, so the contract capture of
    ``analysis/kernel_check.py`` still intercepts it."""
    call = pl.pallas_call(kernel, **kwargs)
    scope = kernel_scope_name(kernel) + (f"_{label}" if label else "")

    def bound(*operands):
        with jax.named_scope(scope):
            return call(*operands)

    return bound

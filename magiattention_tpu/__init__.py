"""magiattention_tpu — a TPU-native distributed-attention framework.

A from-scratch JAX / XLA / Pallas implementation of the capabilities of
MagiAttention (context-parallel attention for ultra-long-context,
heterogeneous-mask training): flex-flash-attention over ``AttnSlice``
metadata, load-balanced sequence dispatch, GroupCast/GroupReduce collectives
over ICI, and a multi-stage compute/comm-overlap CP runtime.
"""

import logging as _logging

from .env.general import log_level as _log_level

__version__ = "0.1.0"

_logger = _logging.getLogger("magiattention_tpu")
if not _logger.handlers:
    _handler = _logging.StreamHandler()
    _handler.setFormatter(
        _logging.Formatter("[%(asctime)s][%(name)s][%(levelname)s] %(message)s")
    )
    _logger.addHandler(_handler)
_logger.setLevel(_log_level())

from . import common, config, env  # noqa: F401, E402
from .config import (  # noqa: F401, E402
    DispatchConfig,
    DistAttnConfig,
    GrpCollConfig,
    OverlapConfig,
)


def __getattr__(name):
    # lazy: the api module pulls in jax; keep `import magiattention_tpu` light
    if name in (
        "magi_attn_flex_key",
        "magi_attn_varlen_key",
        "dispatch",
        "undispatch",
        "calc_attn",
        "get_position_ids",
        "get_document_starts",
        "get_mesh",
        "roll",
        "roll_simple",
        "magi_attn_flex_dispatch",
        "magi_attn_varlen_dispatch",
        "flex_flash_attn_func",
        # reference top-level names (ref __init__.py:86-97)
        "init_dist_attn_runtime_key",
        "init_dist_attn_runtime_mgr",
    ):
        from . import api

        return getattr(api, name)
    # resilience error types (docs/resilience.md): importable from the top
    # level so training loops can catch them without knowing the layout
    if name in (
        "ResilienceError",
        "FaultSpecError",
        "InjectedFault",
        "NumericGuardError",
        "FallbackExhaustedError",
        "PageExhaustedError",
        "UnknownLoweringError",
    ):
        from . import resilience

        return getattr(resilience, name)
    # paged-KV / serving names (kernels/paged_kv.py, kernels/paged_decode.py,
    # serving/): lazy for the same reason as the api block
    if name in (
        "PagedKVCache",
        "paged_attn",
        "paged_decode_attn",
    ):
        from . import kernels

        return getattr(kernels, name)
    if name in (
        "ServeConfig",
        "ServeEngine",
        "ServeRequest",
    ):
        from . import serving

        return getattr(serving, name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")

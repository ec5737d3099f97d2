"""Support utilities (ref: magi_attention/utils/)."""

from .profiling import instrument_scope, switch_profile  # noqa: F401
from .mem_budget import ffa_vmem_budget, ffa_max_total_seqlen  # noqa: F401

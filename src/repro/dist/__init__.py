"""repro.dist — logical sharding rules."""
from repro.dist.sharding import (  # noqa: F401
    Rules, bf16_matmul_out_enabled, current_rules, logical, make_rules,
    param_specs, use_rules, weight_gather_enabled, weight_gather_mode,
)

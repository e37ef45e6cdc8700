from repro.sharding.specs import (
    ShardingRules, param_shardings, cache_shardings, batch_shardings,
    opt_state_shardings, logits_sharding, replicated)

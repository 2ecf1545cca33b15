from repro.distributed.sharding import (SINGLE_POD_RULES, MULTI_POD_RULES,
                                        use_rules, get_rules, shard,
                                        logical_to_pspec, spec_tree)
from repro.distributed import collectives

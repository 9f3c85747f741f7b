"""Distribution layer: DynaComm-bucketed collectives, sharding rules, and
the ZeRO trainer (paper pull/push procedures as real ring collectives)."""

from repro.dist.collectives import (FlatSpec, flatten_tree, gather_bucket,
                                    make_flat_spec, reduce_scatter_bucket,
                                    unflatten_tree)
from repro.dist.sharding import (batch_shardings, cache_shardings,
                                 param_pspec, params_shardings)
from repro.dist.zero import ZeroTrainer

__all__ = [
    "FlatSpec", "make_flat_spec", "flatten_tree", "unflatten_tree",
    "gather_bucket", "reduce_scatter_bucket",
    "param_pspec", "params_shardings", "batch_shardings", "cache_shardings",
    "ZeroTrainer",
]

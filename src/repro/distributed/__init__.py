from .compat import device_count, make_mesh
from .sharding import (BASELINE_RULES, Rules, activation_spec, batch_axes_for,
                       param_partition_specs, param_shardings, rules_for)
from .collectives import ps_sync, ring_allreduce
from .tp import (SERVING_AXIS, SERVING_TP_AXES, serving_cache_specs,
                 serving_mesh_shards, serving_param_specs,
                 validate_serving_tp)

__all__ = ["Rules", "BASELINE_RULES", "rules_for", "param_partition_specs",
           "param_shardings", "activation_spec", "batch_axes_for",
           "ring_allreduce", "ps_sync",
           "device_count", "make_mesh",
           "SERVING_AXIS", "SERVING_TP_AXES", "serving_cache_specs",
           "serving_mesh_shards", "serving_param_specs",
           "validate_serving_tp"]

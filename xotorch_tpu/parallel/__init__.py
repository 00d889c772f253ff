from xotorch_tpu.parallel.mesh import (
  device_bytes,
  make_mesh,
  param_specs_like,
  per_shard_kernel,
  shard_batch,
  shard_cache,
  shard_params,
  spec_for_param,
)
from xotorch_tpu.parallel.zero import (
  moment_bytes_per_device,
  zero1_constraint,
  zero1_shard_opt_state,
)

__all__ = [
  "make_mesh", "shard_params", "shard_batch", "shard_cache", "per_shard_kernel",
  "param_specs_like", "device_bytes",
  "spec_for_param", "zero1_shard_opt_state", "zero1_constraint", "moment_bytes_per_device",
]

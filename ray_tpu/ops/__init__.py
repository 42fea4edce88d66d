"""TPU compute ops: attention kernels, collectives-based primitives."""

from ray_tpu._private.device_policy import enable_compile_cache

# The model stack (models/, parallel/pipeline) imports JAX through this
# package: place the persistent compile cache before the first compile.
enable_compile_cache()

from ray_tpu.ops.flash_attention import attention, flash_attention  # noqa: F401
from ray_tpu.ops.ring_attention import full_attention, ring_attention  # noqa: F401

"""Hot-op kernels (Pallas TPU) and their jnp oracles."""
from ompi_tpu.ops.flash_attention import (  # noqa: F401
    flash_block_update, fold_jnp,
)

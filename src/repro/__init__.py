"""repro: Star Pattern Fragments (SPF) as a production JAX framework.

x64 is enabled framework-wide: the cost accounting and the ragged-expansion
bookkeeping count in int64.  The store's composite sort keys and every
Pallas kernel operand are int32 (Mosaic has no 64-bit vectors; the store
refuses a dictionary whose keys would not fit), so kernel bodies name
their int32 dtypes explicitly.  All neural-model code uses explicit float
dtypes (bf16/f32), so enabling x64 does not change model numerics.
"""

import jax

jax.config.update("jax_enable_x64", True)

__version__ = "0.1.0"

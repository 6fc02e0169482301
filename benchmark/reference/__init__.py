"""Plain references: float32 `jax.numpy`, `highest` matmul precision, a reverse
Python loop over time, no kernel and no scan. They import nothing from the
program's `ops/` or `algos/`; parameters arrive as the program's parameter tree
and are read by name."""

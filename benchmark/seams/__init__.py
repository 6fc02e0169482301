"""Where the correctness check meets the program, one module per algorithm.

A seam produces one seeded rollout with the program's own rollout code and
runs the program's update path on it, as shipped: the network, the advantage
seam (`algos/common.py`: the Pallas kernel on a TPU) and the loss. It returns
the rollout, so that the plain reference (`benchmark/reference/`) can be given
the same data, the program's loss and advantage targets to compare, and the
program's update as traced (`update_jaxpr`), whose types the harness holds to
the configuration's `compute_dtype` (harness.narrow_matmuls)."""

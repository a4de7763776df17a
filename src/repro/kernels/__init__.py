# Pallas TPU kernels for the perf-critical compute layers.
# Each kernel package has:
#   <name>.py  — pl.pallas_call + explicit BlockSpec VMEM tiling
#   ops.py     — the jit'd public wrapper (auto-interpret on CPU)
#   ref.py     — pure-jnp oracle used by the allclose test sweeps
#
# relagg — fused filter+project+group-aggregate (the paper's
#          set-oriented plan hot loop, batch-mode §8.2.6, as
#          one-hot × MXU matmul partial aggregation)

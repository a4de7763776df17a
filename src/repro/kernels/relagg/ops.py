"""Public jit'd wrapper for the relagg kernel (auto-interpret off-TPU)."""
import functools

import jax

from repro.kernels.relagg.relagg import relagg_pallas


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


@functools.partial(jax.jit, static_argnames=("num_groups", "block_rows", "interpret"))
def grouped_aggregate(gid, mask, vals, num_groups, block_rows=None, interpret=None):
    """Fused filter+group+aggregate.  Returns (sums (G, n_aggs), counts (G,)).

    ``interpret=None`` auto-selects: compiled on TPU, interpret elsewhere
    (interpret mode executes the kernel body op by op on the CPU, for
    correctness tests).  ``block_rows=None`` sizes the row tile from the
    group count (``relagg.pick_block_rows``)."""
    if interpret is None:
        interpret = not _on_tpu()
    return relagg_pallas(
        gid, mask, vals, num_groups, block_rows=block_rows, interpret=interpret
    )

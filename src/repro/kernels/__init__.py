"""Pallas TPU kernels for the framework's compute hot spots.

  index_lookup     — batched hierarchical index lookup (the paper's Alg. 1
                     adapted to the MXU: compare-count ranks + one-hot
                     gathers instead of pointer-chase binary search)
  fused_descent    — the whole resident layer prefix in ONE kernel: a
                     (queries, layers) grid walks every query through all
                     pinned layers, per-layer step/band branching selected
                     by a kind vector, parameter planes double-buffered
                     through VMEM by the grid pipeline (serving hot path)
  flash_attention  — causal blockwise attention (GQA, sliding window,
                     logit softcap) for train/prefill
  decode_attention — flash-decode: one-token attention over a long KV
                     cache with partial-softmax accumulation (composes
                     with sequence-sharded KV via shard_map)

Each kernel ships kernel.py (pl.pallas_call + BlockSpec), ops.py (jit'd
public wrapper) and ref.py (pure-jnp oracle).  The serving and tuning
dispatchers (fused_descent, candidate_score) run Pallas in interpret mode
on the CPU and compiled on an accelerator, as :func:`interpret_mode`
decides per call.
"""


def interpret_mode() -> bool:
    """Whether Pallas kernels run in interpret mode: on the CPU backend
    only.  Decided by the platform at dispatch, never by a stored flag, so
    an index written on a CPU host serves compiled on a TPU."""
    import jax
    return jax.default_backend() == "cpu"

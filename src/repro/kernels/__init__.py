"""Pallas TPU kernels for the index's two device hot spots.

  fused_descent    — serving: the whole resident layer prefix in ONE
                     kernel (the paper's Alg. 1 as compare-count ranks and
                     masked row-sums): a (queries, layers) grid walks every
                     query through all pinned layers, per-layer step/band
                     branching selected by a kind vector, parameter planes
                     double-buffered through VMEM by the grid pipeline
  candidate_score  — tuning: batched ``Ê[T(Δ)]`` of AirTune's candidate
                     layers under an affine tier

Each kernel ships kernel.py (pl.pallas_call + BlockSpec), ops.py (the
dispatching public wrapper) and ref.py (oracle).  Both dispatchers run
Pallas in interpret mode on the CPU and compiled on an accelerator, as
:func:`interpret_mode` decides per call.
"""


def interpret_mode() -> bool:
    """Whether Pallas kernels run in interpret mode: on the CPU backend
    only.  Decided by the platform at dispatch, never by a stored flag, so
    an index written on a CPU host serves compiled on a TPU."""
    import jax
    return jax.default_backend() == "cpu"

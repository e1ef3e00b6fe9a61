"""Batched candidate scoring for the AirTune sweep engine.

Evaluates the Eq. (9) ranking estimate ``Ê[T(Δ)]`` for a whole (C, S)
matrix of candidate widths in one shot.  Backends (see
:func:`ops.candidate_scores`):

  * ``pallas`` — fused affine-profile weighted row-mean kernel
    (interpret mode on CPU, native on TPU),
  * ``jnp``    — jitted XLA reduction,
  * ``numpy``  — :func:`repro.core.latency.batched_mean_read_costs`,
    the bit-exact float64 reference and the search default.

Device paths require an affine-representable tier
(:func:`repro.core.storage.affine_coefficients`); anything else falls
back to numpy; a device backend's failure propagates.  They compute in
float32 and are used for candidate *ranking* only — exact Eq. (6) costs
always take the numpy path.
"""
from .ops import affine_candidate_scores, candidate_scores
from .ref import affine_scores_ref

__all__ = ["affine_candidate_scores", "candidate_scores",
           "affine_scores_ref"]

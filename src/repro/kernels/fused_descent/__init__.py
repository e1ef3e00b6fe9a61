from .ops import (MAX_VMEM_ENTRIES, band_f32_slack, fused_descent,
                  fused_descent_with_backend, pack_prefix, prefix_gate,
                  upload_planes)

__all__ = ["MAX_VMEM_ENTRIES", "band_f32_slack", "fused_descent",
           "fused_descent_with_backend", "pack_prefix", "prefix_gate",
           "upload_planes"]

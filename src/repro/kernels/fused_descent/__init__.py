from .ops import (MAX_VMEM_ENTRIES, PLANES, SIGN, band_f32_slack,
                  fused_descent, fused_descent_with_backend, pack_prefix,
                  prefix_gate, split_words, upload_planes)

__all__ = ["MAX_VMEM_ENTRIES", "PLANES", "SIGN", "band_f32_slack",
           "fused_descent", "fused_descent_with_backend", "pack_prefix",
           "prefix_gate", "split_words", "upload_planes"]

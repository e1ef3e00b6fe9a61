"""Flash-decode over a sequence-sharded KV cache (the optimized serve path).

When kv_heads < model-axis size (deepseek/qwen/llama4/grok: 8 kv heads on
a 16-way axis), the baseline shards the cache's *sequence* dim and lets
SPMD insert logit gathers.  This module does it manually with shard_map:
each device computes the partial-softmax triple (o, m, l) over its local
sequence shard — kernels.decode_attention on TPU, its oracle here — and
the shards combine with the numerically-exact max-correction:

    M = pmax(m);  L = psum(l·e^{m−M});  O = psum(o·l·e^{m−M}) / L

Communication per step: 2·(B·Hq) scalars + (B·Hq·hd) — independent of
sequence length, vs the baseline's (B·Hq·S_local) logit gather.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.kernels.decode_attention import ops as da_ops


def flash_decode_sharded(mesh: Mesh, axis: str = "model"):
    """Returns decode_attn(q, ck, cv, kv_length) with seq-sharded ck/cv.

    q (B,Hq,hd) replicated over ``axis``; ck/cv (B,Hkv,S,hd) sharded on S;
    kv_length (B,) global lengths.  Output (B,Hq,hd) replicated.
    """
    n_shards = mesh.shape[axis]

    def local(q, ck, cv, kv_length):
        idx = jax.lax.axis_index(axis)
        S_local = ck.shape[2]
        start = idx * S_local
        # tokens of this shard that are within the global valid length
        local_len = jnp.clip(kv_length - start, 0, S_local)
        o, m, l = da_ops.decode_attention(q, ck, cv, local_len, use_ref=True)
        # all-empty shards contribute exp(-inf)=0 via the m correction
        M = jax.lax.pmax(m, axis)
        w = l * jnp.exp(m - M)
        L = jax.lax.psum(w, axis)
        O = jax.lax.psum(o * w[..., None], axis) / jnp.maximum(
            L, 1e-30)[..., None]
        return O

    in_specs = (P(), P(None, None, axis, None), P(None, None, axis, None),
                P())
    return jax.shard_map(local, mesh=mesh, in_specs=in_specs,
                         out_specs=P(), check_vma=False)

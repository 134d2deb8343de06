"""Shared Pallas TPU tiling helpers.

The house kernels (ops/flash_attention.py training attention, the
ops/decode_kernels.py serving kernels) share the same plumbing: a block
picker that snaps tile sizes to the TPU lane grid and falls back to XLA
when nothing divides, and the finite -inf the online softmaxes mask with.
Keeping them here means one set of heuristics for every kernel instead of
per-file copies.
"""

from __future__ import annotations

# finite stand-in for -inf inside kernels: exp(NEG_INF - m) underflows to
# an exact 0.0 for any live m, so masked lanes never perturb the softmax
# (same invariant jnp.finfo(f32).min gives the XLA paths)
NEG_INF = float(-1e30)


def pick_block(t: int, preferred: int = 512) -> int:
    """Largest of (preferred, 512, 256, 128) that divides ``t``, capped at
    ``preferred``; 0 when nothing divides (caller falls back to XLA)."""
    for b in (preferred, 512, 256, 128):
        if b <= preferred and t % b == 0:
            return b
    return 0

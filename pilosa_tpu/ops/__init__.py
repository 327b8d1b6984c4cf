"""L0 kernels: dense bitmap-plane algebra on TPU.

These are the TPU-native equivalents of the reference's roaring container
kernels (reference: roaring/roaring.go:711-1660) and fragment scan loops
(reference: fragment.go:283-1305) — the XLA/Pallas kernel targets.
"""

from pilosa_tpu.ops.bitmap import (
    plane_and,
    plane_andnot,
    plane_count,
    plane_difference,
    plane_intersection_count,
    plane_not,
    plane_or,
    plane_union,
    plane_xor,
    plane_shift,
    bits_to_plane,
    plane_to_bits,
    plane_range_mask,
    row_counts,
    zero_plane,
)
from pilosa_tpu.ops.bsi import (
    bsi_compare,
    bsi_plane_popcounts,
    bsi_sum,
    bsi_min,
    bsi_max,
)
from pilosa_tpu.ops.groupby import masked_pair_counts, pair_counts
from pilosa_tpu.ops.topk import top_rows

__all__ = [
    "plane_and",
    "plane_andnot",
    "plane_count",
    "plane_difference",
    "plane_intersection_count",
    "plane_not",
    "plane_or",
    "plane_union",
    "plane_xor",
    "plane_shift",
    "bits_to_plane",
    "plane_to_bits",
    "plane_range_mask",
    "row_counts",
    "zero_plane",
    "bsi_compare",
    "bsi_plane_popcounts",
    "bsi_sum",
    "bsi_min",
    "bsi_max",
    "pair_counts",
    "masked_pair_counts",
    "top_rows",
]

from repro.kernels.ivf_topk.ops import (
    DEFAULT_CAP_TILE,
    ivf_topk,
    live_tile_share,
    live_tiles,
    tile_align_index,
)
from repro.kernels.ivf_topk.ref import ivf_topk_ref

__all__ = [
    "ivf_topk",
    "ivf_topk_ref",
    "live_tile_share",
    "live_tiles",
    "tile_align_index",
    "DEFAULT_CAP_TILE",
]

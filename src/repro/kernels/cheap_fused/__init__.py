from repro.kernels.cheap_fused.ops import cheap_fused  # noqa: F401
from repro.kernels.cheap_fused.cheap_fused import DEFAULT_TILE, FusedTile  # noqa: F401

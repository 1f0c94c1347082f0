from repro.kernels.cheap_fused.ops import cheap_fused  # noqa: F401

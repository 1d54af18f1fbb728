from repro.kernels.fps.ops import fps_sample  # noqa: F401

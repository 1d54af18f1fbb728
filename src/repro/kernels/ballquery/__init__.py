from repro.kernels.ballquery.ops import ball_query  # noqa: F401

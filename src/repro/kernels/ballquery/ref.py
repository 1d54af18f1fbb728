"""Oracle for the ball-query kernel: the MPiNets reference's brute force."""
from repro.models.mpinets_ref import ball_query as ball_query_ref  # noqa: F401

"""Oracle for the FPS kernel: the MPiNets reference's brute-force FPS."""
from repro.models.mpinets_ref import fps as fps_ref  # noqa: F401

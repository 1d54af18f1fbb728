# Persistent whole-traversal megakernel: the ENTIRE multi-level wavefront
# walk in one pallas_call — per-tile double-buffered VMEM frontier, in-kernel
# level loop, in-register CSR expansion/compaction, and (for
# scenes past the VMEM residency budget) double-buffered HBM->VMEM streaming
# of per-level node-metadata windows.  The jnp reference arm mirrors it with
# live-prefix width scheduling and models the same window schedule.  Backs
# ``EngineConfig.mode == "wavefront_persistent"`` and the ragged multi-scene
# flat frontier of ``query_batched_scenes``.

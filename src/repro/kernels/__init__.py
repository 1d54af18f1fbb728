# Pallas TPU kernels for the compute hot-spots the paper optimizes:
#   sact/            fused staged OBB-AABB separating-axis test
#                    (the "collision OP unit" of RoboGPU SIII-C)
#   ballquery/       batched first-k ball query of MPiNets' set abstractions
#                    (pointnet2 semantics; RoboGPU SIV's grouping stage)
#   fps/             batched farthest point sampling, every pick in one call
#   wkv6/            RWKV-6 chunked recurrence (rwkv6-1.6b arch)
#   flash_attention/ blockwise online-softmax attention (LM archs)
# Each package: kernel.py (pl.pallas_call + BlockSpec), ops.py (jit wrapper,
# padding, interpret switch), ref.py (pure-jnp oracle used by tests).

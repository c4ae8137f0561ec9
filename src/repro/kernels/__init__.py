# Pallas TPU kernels for K-FAC's compute hot-spots (paper S8 cost model):
#   factor_update   — fused decayed symmetric accumulation C <- eps C + s XᵀX
#   matmul          — tiled MXU matmul with scale/accumulate epilogue
#   ns_step         — Newton–Schulz inverse iteration X <- X(2I − MX)
#   precond         — two-sided preconditioning U = Ā⁻¹ V G⁻¹
#   rotate_rescale  — EKFAC eigenbasis apply Q_A[(Q_AᵀVQ_G)/(s+λ)]Q_Gᵀ with
#                     the damped rescale fused into the middle matmul
#   flash_attention — fwd flash attention (GQA/causal/window/softcap) for the
#                     model substrate's serving path
#   flash_decode    — one-token decode vs a long (sequence-sharded) KV cache;
#                     per-row (B,) valid lengths via scalar prefetch (each
#                     continuous-batching slot masks its own prefix), with
#                     sliding-window and softcap support for gemma2-style
#                     local layers
#   flash_decode_paged — the same against a shared page pool through a
#                     page table (the serving engine's paged route)
#   update_chain    — fused precondition + momentum + ΣD² for the fixed-lr
#                     update; patch_factor — fused im2col + KFC factor update
# ops.py routes decode attention onto the kernels on a TPU (the einsum
# oracle elsewhere); ref.py holds the oracles the tests sweep against
# (interpret mode on CPU); backend.py resolves interpret mode from the
# live backend at call time and hosts the tile_ok gate the curvature
# blocks (core/blocks) use before routing onto these kernels.

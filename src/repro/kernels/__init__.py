# Pallas TPU kernels for the compute hot-spots (validated in interpret mode
# on CPU; selected via ArchConfig attn_impl / ssm_impl / norm_impl, and for
# the MoE grouped matmul by platform):
#   flash_attention  — blocked causal/GQA/SWA attention (train/prefill)
#   decode_attention — flash-decode split-K over the dense KV cache (serve)
#   paged_attention  — flash-decode over a block-pool KV cache: block
#                      tables arrive via scalar prefetch and pick the
#                      physical block each grid step streams into VMEM
#   ssd_scan         — Mamba-2 chunked state-space scan
#   grouped_matmul   — MoE ragged expert matmul (dense-padded tiling)
#   rmsnorm          — fused residual+RMSNorm (memory-bound fusion)

# Pallas TPU kernels for the compute hot spots: ddpg_fused (the paper's
# Table III inner loop — 96 DDPG updates with params resident in VMEM,
# gridded over fleet sessions), flash_attention (fwd+bwd), mamba2_scan
# (chunked SSD), rwkv6 (chunked WKV), gmm (grouped matmul).
# ref.py holds the pure-jnp oracles; ops.py is the dispatch layer
# (Pallas on TPU / XLA fallback on CPU, but the DDPG learner's XLA scan on
# every platform unless REPRO_KERNELS=pallas; REPRO_KERNELS=interpret for
# tests).

"""Pallas TPU kernels for the performance-critical compute layers.

  mlp_softmax_attn.py  the paper's hot spot, algebraically fused:
                       out = relu(QK^T @ W1 + b1) @ (W2 @ V) + b2 @ V —
                       the S x S "probs" matrix never materializes.
  flash_attn.py        exact-softmax flash attention (baseline / targets)
  entropy_head.py      fused softmax+entropy over logits (what MLP_se
                       replaces — the Oracle's scoring op)
  ssd.py               Mamba-2 chunked SSD with VMEM-resident state carry
  rg_lru.py            RG-LRU linear recurrence, chunked time tiles
  secure_matmul.py     int32-ring Beaver matmul combine on int8 digits
                       (TPU MPC path)

Each kernel: pl.pallas_call + explicit BlockSpec VMEM tiling. ops.py is
the jit'd dispatch wrapper (interpret=True off-TPU); ref.py holds the
pure-jnp oracles the tests sweep against.
"""
from repro.kernels import ops, ref

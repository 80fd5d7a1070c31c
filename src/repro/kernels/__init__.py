"""Pallas TPU kernels for the compute hot-spots of the paper's technique,
adapted to the TPU memory hierarchy (DESIGN.md §3):

  quant_matmul        -- int8/int4-grid weights dequantized HBM->VMEM (paper's
                         quantization: cuts the decode memory-roofline term)
  clustered_matmul    -- codebook+index weights reconstructed in VMEM (paper's
                         weight clustering: the shareable unit on TPU is an
                         HBM transfer, not a product wire)
  block_sparse_matmul -- zero (bk,bn) tiles skipped via pl.when (paper's
                         pruning: the MXU's skippable unit is a tile)
  flash_attention     -- online-softmax attention, causal + sliding window
                         (keeps scores in VMEM; the memory-roofline fix for
                         the attention-heavy cells)
  ssm_scan            -- Mamba-1 selective scan with the time loop inside the
                         kernel and the recurrent state in VMEM scratch (the
                         TPU-native analogue of the CUDA selective_scan)

Each kernel ships kernel.py (pl.pallas_call + BlockSpec), ops.py (jit'd
wrapper; interpret=True on CPU) and ref.py (oracle); tests sweep
shapes/dtypes and assert bit-exactness / allclose against the oracle.

netlist_sim, the engine behind the default netlist-exact GA objective, is
no Pallas kernel: population-batched printed-netlist simulation as one
jitted `lax.scan` over a host-built wave schedule (ops.py), with its
packing (pack.py) and NumPy oracle (ref.py).
"""

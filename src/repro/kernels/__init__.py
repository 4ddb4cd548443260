"""Pallas TPU kernels for the framework's compute hot spots:

* flash_attention — blocked online-softmax attention, forward and backward
  (the training path's causal self-attention on the TPU)
* ssd_scan — fused Mamba-2 SSD chunked scan (mamba2/jamba cells)

Each kernel ships with a jit wrapper (ops.py) and a pure-jnp oracle
(ref.py); tests sweep shapes/dtypes in interpret mode on CPU.
"""

from repro.kernels.ops import flash_attention, ssd_scan

__all__ = ["flash_attention", "ssd_scan"]

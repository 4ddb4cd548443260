"""Pallas TPU kernels for the framework's compute hot spots:

* flash_attention — blocked online-softmax attention, forward and backward
  (the training path's causal self-attention on the TPU)
* ssd_scan — fused Mamba-2 SSD chunked scan, forward and backward (the
  cache-less Mamba-2 scan on the TPU: training, and prefill on one chip)

Each kernel ships with a jit wrapper (ops.py) and a pure-jnp oracle
(ref.py); tests sweep shapes/dtypes in interpret mode on CPU.
"""

from repro.kernels.ops import flash_attention, ssd_scan

__all__ = ["flash_attention", "ssd_scan"]

"""Pallas TPU kernels for the framework's compute hot spots.

flash_attention   — blockwise online-softmax attention (train/prefill)
decode_attention  — streaming GQA decode over the KV cache
ssd_scan          — Mamba2 SSD intra-chunk kernel
ssm_decode        — Mamba2 decode step, in place on the stacked state

Each has a pure-jnp oracle in ``ref.py`` and a jit'd wrapper in ``ops.py``
(interpret-mode on CPU, compiled on TPU).  The paper itself contributes
measurement infrastructure, not kernels — these serve the workload side.
"""

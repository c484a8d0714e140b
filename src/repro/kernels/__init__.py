"""Pallas TPU kernels (validated on CPU via interpret=True).

  flash_attention — blocked online-softmax attention (fwd) + recompute VJP
  rwkv6           — chunked linear-recurrence (RWKV6 / Mamba2 SSD hot loop)
  phase_max       — segment-max over CSR phase loads (fair-share inner loop)
  ops             — jit'd wrappers with implementation={"xla","pallas"}
  ref             — pure-jnp oracles
"""
from .ops import attention, flash_attention, rwkv6_mix
from .phase_max import phase_worst_pallas

"""Pallas TPU kernels for DISC-JAX's performance-critical fused patterns.

Each kernel directory holds:
  <name>.py — the pallas_call + BlockSpec VMEM tiling (TPU target;
              ``platform.pallas_call`` interprets it on the CPU),
  ops.py    — jit'd wrapper incl. shape-adaptive version selection (§4.3),
  ref.py    — pure-jnp oracle used by the test sweeps.
"""

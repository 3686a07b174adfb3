"""Pallas TPU kernels for the compute hot-spots, with jnp oracles.

kernels: flash_attention (prefill), decode_attention (flash-decoding),
ssd_scan (Mamba2 SSD), rmsnorm (fused norm), expert_gmm (routed experts'
grouped matmul).  See ops.py for the public
wrappers and ref.py for the allclose oracles.
"""
from . import ops, ref

__all__ = ["ops", "ref"]

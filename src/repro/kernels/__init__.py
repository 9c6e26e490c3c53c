"""Pallas TPU kernels: moe_gmm (grouped expert matmul), decode_attn
(GQA flash-decode; calibration's TPU attention timing only, not on the
served decode path), mla_decode (latent-attention flash-decode over a
stacked latent cache), moe_decode (the decode expert FFN over only the
experts a batch hits, from the layer stacks), deposit (fleet-sim
scatter-add work binning).
ops.py = jit wrappers, ref.py = jnp oracles."""
from . import ops, ref

__all__ = ["ops", "ref"]

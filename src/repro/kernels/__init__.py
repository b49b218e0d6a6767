# Pallas TPU kernels for the compute hot-spots (validated interpret=True on
# CPU; see tests/test_kernels.py for the shape/dtype sweeps vs ref.py):
#   gossip_mix      — the paper's per-step (w + w_recv)/2 fused elementwise
#   fused_update    — single-sweep fused mix+apply (gossip arrival mix +
#                     SGD/AdamW/LARS update, one HBM pass per bucket)
#   quantize        — int8/fp8 wire encode + per-tile-scale decode (the
#                     compressed gossip wire; decode folds into the sweeps)
#   ssm_scan        — chunked Mamba selective scan (falcon-mamba / jamba)
#   flash_attention — causal GQA attention, forward + dK/dV + dQ kernels
#                     (the train path's attention on a TPU)
from .ops import (interpret, flash_mha, fused_adamw_bucket, fused_lars_bucket,
                  fused_sgd_bucket, gossip_mix_bucket, gossip_mix_flat,
                  gossip_mix_tree, gossip_mix_wire_bucket, ssm_scan)
from .quantize import (WIRE_DTYPES, WireFormat, decode_wire, dequant_tiles,
                       encode_wire, payload_spec, wire_itemsize, wire_key,
                       wire_uniform, zero_payload_like)
from . import ref

"""Pallas TPU kernels for the perf-critical compute paths:

  slot_step        — fused fleet slot-step: Balanced-PANDAS workload +
                     private-route argmin (`fleet_route`)
  maxweight        — batched JSQ-MaxWeight weighted argmax claims
  flash_attention  — block-wise online-softmax attention (GQA/SWA/softcap)
  ssd_scan         — Mamba-2 SSD chunked scan

Public API lives in ops.py (padding + interpret fallback); oracles in ref.py.
"""

from repro.kernels.ops import (  # noqa: F401
    flash_attention, fleet_route, maxweight_claim, ssd,
)

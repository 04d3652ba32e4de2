"""Pallas TPU kernels (validated in interpret mode on CPU).

  lazy_prox       — Lemma-11 recovery catch-up (the paper's Section 6)
  fused_prox_svrg — fused VR-gradient + elastic-net prox inner update
"""
from repro.kernels.ops import lazy_prox, fused_prox_svrg

__all__ = ["lazy_prox", "fused_prox_svrg"]

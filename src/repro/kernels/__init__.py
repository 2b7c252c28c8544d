"""Pallas TPU kernels for the substrate's compute hot spots (the paper itself
has no kernel-level contribution — see DESIGN.md Sec. 2.3): flash attention,
RG-LRU recurrence, min-plus matmul, and the Mamba-2 SSD scan.  The MoE
layer's grouped matmul is JAX's megablox kernel (``models.layers``).

Each kernel: <name>.py (pl.pallas_call + explicit BlockSpec VMEM tiling),
ops.py (wrappers), ref.py (pure-jnp oracles).  Validated in interpret mode on
CPU; Mosaic lowering on real TPUs.  The SSD scan's forward and backward
kernels (ssd.py) are the train step's SSD path on a TPU where the shapes tile
(``models.layers`` chooses by the platform lowered for and by shape); its
oracle, and the path everywhere else, is ``models.layers._ssd_chunked``.
Likewise the flash-attention pair (flash_attention.py) is the training
path's attention on a TPU (``models.layers._train_attention``); its oracle,
and the path everywhere else, is ``models.layers.blocked_attention``.
"""
from . import ops, ref
from .flash_attention import flash_attention
from .minplus import minplus_matmul
from .rglru import rglru_scan

__all__ = ["ops", "ref", "flash_attention", "minplus_matmul",
           "rglru_scan"]

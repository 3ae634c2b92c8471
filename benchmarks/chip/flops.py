"""A kernel's share of its roofline (no program code).

The operations and bytes a kernel or a step needs come from the sizes in
a configuration file, and so from the reference module that file names
(``reference.py``'s ``train_flops_per_token`` and
``flash_attention_cost`` for the dense Qwen decoders).
"""
from __future__ import annotations


def roofline(flops: float, nbytes: float, seconds: float, peak: dict):
    """-> (share of the roofline in %, the bound: 'compute' or 'memory')."""
    t_c = flops / peak["bf16_flops_per_s"]
    t_m = nbytes / peak["hbm_bytes_per_s"]
    return 100.0 * max(t_c, t_m) / seconds, ("compute" if t_c >= t_m
                                              else "memory")

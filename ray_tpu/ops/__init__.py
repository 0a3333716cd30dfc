"""TPU kernels (pallas) for the hot ops.

The compute path of the framework is XLA-compiled jax; these kernels
cover the places where XLA's fusion leaves HBM bandwidth on the table —
first of all attention, whose materialized [B,H,T,T] score matrix
dominates memory traffic at pretraining shapes (``flash_attention``),
and whose gathered copy of the paged KV pool dominated a serving decode
step (``paged_attention.paged_decode``); then the delta rule's decode
step, which XLA walks three times over a layer's states where one read
and one write do (``delta_rule.kda_step``).
"""

from .flash_attention import (flash_attention,  # noqa: F401
                              flash_attention_qkv)

"""Model FLOPs of one training step, from a configuration's shapes.

Counted: every matrix product of the forward pass, times 3 for forward plus
backward (the backward takes two products per forward product). Causal
attention counts the half of the score and value products that the mask
keeps. Recomputation under remat does not count: it is work the model does
not need. The embedding lookup is a gather and counts nothing. Elementwise
work counts only where it is the layer's own arithmetic (the Mamba scan);
norms, activations and the loss's softmax are left out. Each layer part
(``bench/layers/``) counts its own; the period repeats ``n_blocks`` times.
"""
from __future__ import annotations

from bench import layers


def _parts(a: dict):
    return [p for pair in layers.period(a) for p in pair]


def matmul_params(a: dict) -> int:
    """Weights that take part in a matrix product, per token: the layers
    and the output head (tied or not)."""
    per_period = sum(p.matmul_params(a) for p in _parts(a))
    return layers.n_blocks(a) * per_period + a["hidden_size"] * a["vocab_size"]


def layer_flops_fwd(a: dict, batch: int, seq: int) -> int:
    """The layers' forward FLOPs outside their weights' products: causal
    attention's scores and values, the scan's elementwise work."""
    return layers.n_blocks(a) * sum(p.flops_fwd(a, batch, seq)
                                    for p in _parts(a))


def train_step_flops(a: dict, batch: int, seq: int) -> float:
    tokens = batch * seq
    return 3.0 * (2.0 * matmul_params(a) * tokens
                  + layer_flops_fwd(a, batch, seq))

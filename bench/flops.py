"""Model FLOPs of one training step, from a configuration's shapes.

Counted: every matrix product of the forward pass, times 3 for forward plus
backward (the backward takes two products per forward product). Causal
attention counts the half of the score and value products that the mask
keeps. Recomputation under remat does not count: it is work the model does
not need. The embedding lookup is a gather and counts nothing. Elementwise
work counts only where it is the layer's own arithmetic (the Mamba scan);
norms, activations and the loss's softmax are left out.
"""
from __future__ import annotations


def matmul_params(a: dict) -> int:
    """Weights that take part in a matrix product, per token."""
    d, V, L = a["hidden_size"], a["vocab_size"], a["num_hidden_layers"]
    if a["kind"] == "transformer":
        h, kv, dh, f = (a["num_attention_heads"], a["num_key_value_heads"],
                        a["head_dim"], a["intermediate_size"])
        layer = d * h * dh + 2 * d * kv * dh + h * dh * d + 3 * d * f
    else:
        di, ds, dr = (a["intermediate_size"], a["state_size"],
                      a["time_step_rank"])
        layer = d * 2 * di + di * (dr + 2 * ds) + dr * di + di * d
    return L * layer + d * V                       # layers + output head


def attention_flops_fwd(a: dict, batch: int, seq: int) -> float:
    """QK^T and PV of causal attention, forward: 2 products of
    2*S*S*H*dh each, half of it under the mask."""
    if a["kind"] != "transformer":
        return 0.0
    h, dh = a["num_attention_heads"], a["head_dim"]
    return a["num_hidden_layers"] * batch * 2 * seq * seq * h * dh


def scan_flops_fwd(a: dict, tokens: int) -> float:
    """Selective scan per token and channel x state: exp(dt*A) (1), dt*u*B
    (2), h = a*h + b (2), y += h*C (2); plus the depthwise conv (2 per tap)."""
    if a["kind"] != "mamba":
        return 0.0
    di, ds, dc = a["intermediate_size"], a["state_size"], a["conv_kernel"]
    return a["num_hidden_layers"] * tokens * (7 * di * ds + 2 * dc * di)


def train_step_flops(a: dict, batch: int, seq: int) -> float:
    tokens = batch * seq
    fwd = (2.0 * matmul_params(a) * tokens + attention_flops_fwd(a, batch, seq)
           + scan_flops_fwd(a, tokens))
    return 3.0 * fwd

"""The gated MLP, ``w2(act(x w1) * (x w3))`` with SiLU: an FFN."""
import math

import jax

from bench.layers import normal

ROLE = "ffn"
SPEC = "dense"
KEYS = ("intermediate_size", "hidden_act")
SUBKEY = 2


def arch_fields(a: dict) -> dict:
    return {"d_ff": a["intermediate_size"], "act": a["hidden_act"]}


def init(key, a: dict, dtype) -> dict:
    d, f = a["hidden_size"], a["intermediate_size"]
    km = jax.random.split(key, 3)
    return {"w1": normal(km[0], (d, f), 1 / math.sqrt(d), dtype),
            "w3": normal(km[1], (d, f), 1 / math.sqrt(d), dtype),
            "w2": normal(km[2], (f, d), 1 / math.sqrt(f), dtype)}


def forward(p, h, a, mm):
    if a["hidden_act"] != "silu":
        raise ValueError(f"bench: mlp runs silu, not {a['hidden_act']!r}")
    g = jax.nn.silu(mm("bsd,df->bsf", h, p["w1"]))
    return mm("bsf,fd->bsd", g * mm("bsd,df->bsf", h, p["w3"]), p["w2"]), None


def matmul_params(a: dict) -> int:
    return 3 * a["hidden_size"] * a["intermediate_size"]


def flops_fwd(a: dict, batch: int, seq: int) -> int:
    return 0

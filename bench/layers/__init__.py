"""The layer parts that a configuration's ``period`` names, one module per
mixer or FFN: ``bench/layers/<part>.py``, found by its name.

A configuration file states its repeating pattern of layers as
``"period": [{"mixer": <part>, "ffn": <part>}, ...]``; layer ``l`` of the
model is position ``l % len(period)`` of block ``l // len(period)``, as in
the program's ``ArchConfig.block``. A new kind of layer is a new part file.

A part module imports nothing of the program under test and gives:

* ``ROLE``: ``"mixer"`` or ``"ffn"``;
* ``SPEC``: the name of the program's ``LayerSpec.mixer`` or ``.ffn`` it
  stands for;
* ``KEYS``: the configuration keys it reads;
* ``arch_fields(a)``: the program's ``ArchConfig`` fields it sets, a nested
  spec (such as ``mamba``) as a dict of that dataclass's fields;
* ``SUBKEY`` and ``init(key, a, dtype)``: its seeded leaves, drawn from the
  sub-key ``SUBKEY`` of the eight that the program's layer init splits its
  layer key into (0 for a mixer, 2 for an MLP, 3 for an MoE), under names
  that no other part and neither layer norm (``norm1``, ``norm2``) uses;
* ``forward(p, x, a, mm)``: its float32 output for the normed input ``x``,
  and a scalar added to the loss, such as a router's balance loss, or
  ``None``; ``mm(spec, x, w)`` is the reference's matrix product;
* ``matmul_params(a)``: its weights that take part in a matrix product, per
  token, and ``flops_fwd(a, batch, seq)``: its other forward FLOPs.

An FFN part with ``SUBKEY = None`` has no leaves: it adds no norm and no
residual branch to its layer, and has no ``init`` or ``forward``.
"""
from __future__ import annotations

import importlib
from typing import List, Tuple

import jax
import jax.numpy as jnp

ROLES = ("mixer", "ffn")


def part(name: str):
    """The part module ``bench/layers/<name>.py``."""
    if not name.isidentifier():
        raise ValueError(f"bench: no layer part named {name!r}")
    return importlib.import_module(f"{__name__}.{name}")


def period(a: dict) -> List[Tuple[object, object]]:
    """(mixer part, ffn part) of each position of the configuration's
    period, as ``dims`` gives it in ``a["period"]``."""
    out = []
    for names in a["period"]:
        mods = tuple(part(n) for n in names)
        for role, mod in zip(ROLES, mods):
            if mod.ROLE != role:
                raise ValueError(f"bench: layer part {mod.__name__!r} is a "
                                 f"{mod.ROLE}, named as a {role}")
        out.append(mods)
    return out


def n_blocks(a: dict) -> int:
    """How many times the period repeats over the depth."""
    return a["num_hidden_layers"] // len(a["period"])


def normal(key, shape, scale, dtype, divide=False):
    """The program's seeded initializer: a float32 standard normal, times
    (or over) ``scale``, cast to ``dtype``."""
    x = jax.random.normal(key, shape, jnp.float32)
    if scale is not None:
        x = x / scale if divide else x * scale
    return x.astype(dtype)

"""``SlotServer`` on reduced configs, on CPU."""
import pytest

import jax
import jax.numpy as jnp

from repro.configs import ARCHS, reduced
from repro.models import model as M
from repro.serving import SlotServer


def _serve_one(server, prompt_token, n_tokens):
    rid = server.submit(prompt_token=prompt_token)
    while len(server.outputs[rid]) < n_tokens:
        server.step()
    return server.finish(rid)


@pytest.mark.parametrize("name", ["falcon-mamba-7b", "internlm2-1.8b"])
def test_slot_reuse_starts_fresh(name):
    """A request served in a slot another request used before gets the
    tokens it gets in a fresh slot: no cache state carries over."""
    full = ARCHS[name]
    cfg = reduced(full, n_layers=2 * len(full.block))
    params = M.init_params(jax.random.PRNGKey(0), cfg, jnp.float32)
    server = SlotServer(params, cfg, M.Runtime(q_chunk=16, cross_len=16),
                        n_slots=1, max_len=32)
    first = _serve_one(server, 5, 6)
    again = _serve_one(server, 5, 6)
    assert len(first) == 6
    assert again == first

"""The state readings that the comparison takes: in set-up the train state
is only copied to the host, and the norms worked out from the copies after
the window are those of the state itself."""
import math

import numpy as np
import pytest

import bench_tiny  # noqa: F401  (puts the checkout on sys.path)

import jax.numpy as jnp

from bench import harness
from repro.training import quant

B1 = 0.9


def _tree(rng, scale):
    def leaf(*shape):
        return jnp.asarray(rng.standard_normal(shape) * scale, jnp.bfloat16)
    return {"layers": [{"w": leaf(3, 10, 12), "norm1": leaf(3, 12)}],
            "embed": leaf(30, 12), "final_norm": leaf(12),
            "unembed": leaf(12, 30)}


def _flat(tree):
    return {**tree["layers"][0],
            **{k: tree[k] for k in ("embed", "final_norm", "unembed")}}


def _norm(x):
    return math.sqrt(float(np.sum(np.square(np.asarray(x, np.float64)))))


def test_copies_are_on_the_host_with_the_trees_shape():
    copy, _ = harness.state_readers(B1)
    m = {"w": quant.quant(jnp.ones((4, 8))), "b": jnp.zeros((8,))}
    got = copy(m)
    assert quant.is_qtensor(got["w"])
    assert isinstance(got["w"].q, np.ndarray) and got["w"].q.dtype == np.int8
    assert isinstance(got["b"], np.ndarray)


def test_change_norms_from_the_copies_are_the_states():
    copy, read = harness.state_readers(B1)
    rng = np.random.default_rng(3)
    init, d1, d2 = _tree(rng, 1.0), _tree(rng, 0.01), _tree(rng, 0.02)
    p1 = {"layers": [{k: v + d1["layers"][0][k]
                      for k, v in init["layers"][0].items()}],
          **{k: init[k] + d1[k] for k in ("embed", "final_norm", "unembed")}}
    p2 = {"layers": [{k: v + d2["layers"][0][k]
                      for k, v in init["layers"][0].items()}],
          **{k: init[k] + d2[k] for k in ("embed", "final_norm", "unembed")}}
    m1 = {"layers": [{k: quant.quant(v.astype(jnp.float32))
                      for k, v in d1["layers"][0].items()}],
          **{k: d1[k] for k in ("embed", "final_norm", "unembed")}}
    r = read(copy(init), copy(m1), copy(p1), copy(p2))
    fi = _flat(init)
    for name, p in (("change1_leaf", p1), ("change_last_leaf", p2)):
        fp = _flat(p)
        assert set(r[name]) == set(fp)
        for k in fp:
            want = _norm(np.asarray(fp[k], np.float32) -
                         np.asarray(fi[k], np.float32))
            assert r[name][k] == pytest.approx(want, rel=1e-5)
    for k, x in _flat(m1).items():
        x = quant.dequant(x) if quant.is_qtensor(x) else x
        assert r["grad_leaf"][k] == pytest.approx(
            _norm(np.asarray(x, np.float32)) / (1 - B1), rel=1e-5)

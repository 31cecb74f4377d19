"""Plain float32 reference of the trained models: weights from the seed,
the corpus's batches, the forward pass, the loss, its gradient and AdamW.

It imports nothing of the program under test. It follows the configuration
file as run (``bench/configs/<name>.json``): the same architecture, the same
seeded weight recipe, the same batches, the same optimizer settings. Every
matrix product runs in float32 at ``highest`` precision; parameters are
stored after each update in the configuration's parameter dtype, as the
configuration states, and the AdamW moments are stored between steps in the
configuration's moment dtype (int8 with a per-row scale). Departures from the
program's own arithmetic are listed in PERF.md.

The architecture is the configuration's ``period`` of layer parts
(``bench/layers/``). The model runs layer by layer (forward keeps each
layer's input; backward re-runs one layer under ``jax.vjp``; one compiled
program per position of the period) and the output head in blocks of
tokens, so that a 1.9 B-parameter model, its stored moments and its float32
gradient fit on one 16 GB chip: gradients go to the host as each layer
finishes.

``precision="fp8"`` is the control: the same computation with every matrix
product's operands rounded to float8 (e4m3 forward, e5m2 for the cotangents,
per-tensor scale), the precision below the configuration's bfloat16.
"""
from __future__ import annotations

import gc
import math
from functools import partial
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from bench import layers
from bench.layers import normal

F32 = jnp.float32
HEAD_BLOCK = 1024     # tokens per call of the output head: its float32 logits
                      # and their gradient stay under ~1 GB at vocab 92544


# ---------------------------------------------------------------------------
# the corpus: the same seeded shards as the training feed's source
# ---------------------------------------------------------------------------


def corpus_batch(seed: int, step: int, vocab: int, seq_len: int,
                 batch_size: int, per_batch: int = 2) -> np.ndarray:
    """Tokens [batch_size, seq_len + 1] of training step ``step`` (1-based):
    ``per_batch`` consecutive shards, each ``batch_size // per_batch``
    sequences of ``seq_len + 1`` ids from its own seeded generator."""
    per_shard = batch_size // per_batch
    rows = []
    for i in range((step - 1) * per_batch, step * per_batch):
        rng = np.random.default_rng(seed * 100_003 + i)
        toks = rng.integers(0, vocab, per_shard * (seq_len + 1), dtype=np.int32)
        rows.append(toks.reshape(per_shard, seq_len + 1))
    return np.concatenate(rows, axis=0)[:batch_size]


# ---------------------------------------------------------------------------
# seeded weights
# ---------------------------------------------------------------------------


def _layer_init(key, a: dict, mixer, ffn, dtype) -> Dict[str, jax.Array]:
    """One layer's leaves: ``norm1`` and the mixer's, then ``norm2`` and the
    FFN's where it has any; each part from its sub-key of the program's
    eight."""
    d = a["hidden_size"]
    ks = jax.random.split(key, 8)
    p = {"norm1": jnp.zeros((d,), dtype)}
    p.update(mixer.init(ks[mixer.SUBKEY], a, dtype))
    if ffn.SUBKEY is not None:
        p["norm2"] = jnp.zeros((d,), dtype)
        p.update(ffn.init(ks[ffn.SUBKEY], a, dtype))
    return p


def init_params(seed: int, a: dict, dtype) -> Dict[str, object]:
    """The seeded weights: ``embed`` [V, d], ``final_norm`` [d], ``unembed``
    [d, V] where the head is not tied, and ``layers``: per position of the
    period, its leaves stacked over the blocks. Position ``i`` is made from
    key ``8 + i`` of the seed's, as the program makes it."""
    key = jax.random.PRNGKey(seed)
    per = layers.period(a)
    ks = jax.random.split(key, 8 + len(per))
    d, V = a["hidden_size"], a["vocab_size"]
    n = layers.n_blocks(a)
    out = {"embed": normal(ks[0], (V, d), None, dtype),
           "final_norm": jnp.zeros((d,), dtype),
           "layers": [jax.vmap(partial(_layer_init, a=a, mixer=m, ffn=f,
                                       dtype=dtype))(
                          jax.random.split(ks[8 + i], n))
                      for i, (m, f) in enumerate(per)]}
    if not a["tie_word_embeddings"]:
        out["unembed"] = normal(ks[1], (d, V), math.sqrt(d), dtype,
                                divide=True)
    return out


# ---------------------------------------------------------------------------
# float8 rounding for the control
# ---------------------------------------------------------------------------


def _round_to(x, dtype):
    amax = jnp.max(jnp.abs(x))
    scale = jnp.where(amax > 0, float(jnp.finfo(dtype).max) / amax, 1.0)
    return ((x * scale).astype(dtype).astype(F32) / scale).astype(x.dtype)


@jax.custom_vjp
def _fp8(x):
    return _round_to(x, jnp.float8_e4m3fn)


def _fp8_fwd(x):
    return _fp8(x), None


def _fp8_bwd(_, g):
    return (_round_to(g, jnp.float8_e5m2),)


_fp8.defvjp(_fp8_fwd, _fp8_bwd)


def _mm(precision: str):
    """einsum of float32 operands, rounded to float8 first in the control."""
    def mm(spec, x, w):
        x, w = x.astype(F32), w.astype(F32)
        if precision == "fp8":
            x, w = _fp8(x), _fp8(w)
        return jnp.einsum(spec, x, w, precision=jax.lax.Precision.HIGHEST)
    return mm


# ---------------------------------------------------------------------------
# layers (float32)
# ---------------------------------------------------------------------------


def rms_norm(x, scale, eps):
    x = x.astype(F32)
    return (x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)
            * (1.0 + scale.astype(F32)))


def layer_forward(p, x, a, pos, precision="f32"):
    """Layer at position ``pos`` of the period: the mixer's residual branch,
    then the FFN's. Returns the output and the sum of the parts' loss terms
    (``None`` where no part has one)."""
    mixer, ffn = layers.period(a)[pos]
    mm = _mm(precision)
    eps = a["rms_norm_eps"]
    y, aux = mixer.forward(p, rms_norm(x, p["norm1"], eps), a, mm)
    x, terms = x + y, [aux]
    if ffn.SUBKEY is not None:
        y, aux = ffn.forward(p, rms_norm(x, p["norm2"], eps), a, mm)
        x, terms = x + y, terms + [aux]
    terms = [t for t in terms if t is not None]
    return x, (sum(terms) if terms else None)


def head_loss_sum(head, x, labels, a, z_loss, precision="f32"):
    """Sum over the rows of x of the next-token loss (cross-entropy plus the
    z-loss ``z_loss * logsumexp**2``); x [B, S, d], labels [B, S]. A tied
    head (no ``unembed``) reads the embedding, with no scale."""
    mm = _mm(precision)
    h = rms_norm(x, head["final_norm"], a["rms_norm_eps"])
    if "unembed" in head:
        logits = mm("bsd,dv->bsv", h, head["unembed"])
    else:
        logits = mm("bsd,vd->bsv", h, head["embed"])
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.sum(lse - gold + z_loss * lse * lse)


def _moment_codec(dtype: str):
    """(save, load): the AdamW moments as the configuration stores them
    between steps: float32, bfloat16, or int8 with one absmax scale per row
    of the last axis (``round(x / scale)``, ``scale = max|x| / 127``)."""
    if dtype == "int8":
        def save(x):
            scale = jnp.max(jnp.abs(x), axis=-1, keepdims=True) / 127.0 + 1e-20
            return jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8), scale

        def load(qs):
            return qs[0].astype(F32) * qs[1]
        return save, load
    if dtype == "bfloat16":
        return (lambda x: x.astype(jnp.bfloat16)), (lambda x: x.astype(F32))
    return (lambda x: x), (lambda x: x)


def flat_leaves(tree) -> Dict[str, jax.Array]:
    """{leaf name: leaf} of a parameter-shaped tree, the reference's or the
    program's, the layer leaves stacked over the blocks: ``embed``,
    ``final_norm``, ``unembed`` where the head is not tied, and e.g. ``wq``;
    with more than one position in the period, ``<position>.wq``."""
    if "layers" in tree:
        positions = tree["layers"]
    else:                                   # the program's nesting
        positions = []
        for block in tree["blocks"]:
            flat = {}
            for k, v in block.items():
                flat.update(v if isinstance(v, dict) else {k: v})
            positions.append(flat)
    out = {}
    for i, pos in enumerate(positions):
        out.update({(f"{i}.{k}" if len(positions) > 1 else k): v
                    for k, v in pos.items()})
    out.update({k: tree[k] for k in ("embed", "final_norm", "unembed")
                if k in tree})
    return out


# ---------------------------------------------------------------------------
# one training step, layer by layer
# ---------------------------------------------------------------------------


class Reference:
    """Drives the seeded model through training steps on given batches.

    The train state lives on the device as the configuration states it:
    parameters in the parameter dtype, the AdamW moments in the moment
    dtype. Each step's float32 gradient goes to the host layer by layer,
    and the update applies it leaf by leaf once the clip scale is known."""

    def __init__(self, a: dict, train: dict, opt: dict, seed: int,
                 precision: str = "f32"):
        self.a, self.train, self.opt, self.seed = a, train, opt, seed
        self.precision = precision
        self.pdtype = jnp.dtype(train["param_dtype"])
        self.params = init_params(seed, a, self.pdtype)
        save, load = _moment_codec(train["moment_dtype"])
        zeros = jax.tree.map(lambda p: save(jnp.zeros(p.shape, F32)),
                             self.params)
        self.m, self.v = zeros, zeros
        self.count = 0
        self.grads: Dict = {}        # the last step's gradient, on the host
        self.scale = 1.0             # its clip scale
        a_, prec = self.a, precision
        z = float(train["z_loss"])

        def layer_at(stack, i):      # float32 copy: float32 gradients
            return jax.tree.map(lambda t: t[i].astype(F32), stack)

        # ``stack``: one position's leaves; ``i``: the block; ``pos``: the
        # position, static, so each position compiles once
        @partial(jax.jit, static_argnums=3)
        def fwd(stack, i, x, pos):
            return layer_forward(layer_at(stack, i), x, a_, pos, prec)

        @partial(jax.jit, static_argnums=4)
        def bwd(stack, i, x, g, pos):
            lp = layer_at(stack, i)
            (_, aux), pull = jax.vjp(
                lambda q, y: layer_forward(q, y, a_, pos, prec), lp, x)
            gp, gx = pull((g, None if aux is None else jnp.ones((), F32)))
            sq = sum(jnp.sum(jnp.square(t)) for t in jax.tree.leaves(gp))
            return gp, gx, sq

        @jax.jit
        def head(hp, x, labels):
            hp = jax.tree.map(lambda t: t.astype(F32), hp)
            loss, pull = jax.vjp(
                lambda q, y: head_loss_sum(q, y, labels, a_, z, prec), hp, x)
            ghp, gx = pull(jnp.ones((), F32))
            return loss, ghp, gx

        @partial(jax.jit, static_argnums=2)
        def embed_grad(tokens, gx, vocab):
            return jnp.zeros((vocab, gx.shape[-1]), F32).at[tokens].add(gx)

        o = opt

        @partial(jax.jit, static_argnums=5)
        def upd(p, g, m, v, coef, move):
            lr, b1c, b2c, scale = coef[0], coef[1], coef[2], coef[3]
            g = g * scale
            m32 = o["b1"] * load(m) + (1 - o["b1"]) * g
            v32 = o["b2"] * load(v) + (1 - o["b2"]) * g * g
            p32 = p.astype(F32)
            step = (m32 / b1c) / (jnp.sqrt(v32 / b2c) + o["eps"]) \
                + o["weight_decay"] * p32
            new_p = (p32 - lr * step).astype(p.dtype) if move else p
            return new_p, save(m32), save(v32)

        @jax.jit
        def moment_norms(m):
            return {k: jnp.sqrt(jnp.sum(jnp.square(load(x))))
                    for k, x in flat_leaves(m).items()}

        self._fwd, self._bwd, self._head, self._embed_grad, self._upd = (
            fwd, bwd, head, embed_grad, upd)
        self._moment_norms = moment_norms

    def loss_and_grad(self, tokens: np.ndarray, rows: slice = slice(None)):
        """Mean loss over the batch's tokens and the gradient norm; the
        float32 gradient goes to the host for the update. ``rows`` selects
        the sequences that count (all of them, except in a planted fault)."""
        toks = jnp.asarray(tokens[rows])
        x_in, labels = toks[:, :-1], toks[:, 1:]
        n_tok = labels.size
        P = self.params
        n_pos = len(P["layers"])
        x = jnp.take(P["embed"], x_in, axis=0).astype(F32)
        xs, aux_sum = [], 0.0
        for layer in range(self.a["num_hidden_layers"]):
            pos, block = layer % n_pos, layer // n_pos
            xs.append(x)
            x, aux = self._fwd(P["layers"][pos], block, x, pos)
            if aux is not None:
                aux_sum += float(aux)
        head_w = "unembed" if "unembed" in P else "embed"
        hp = {"final_norm": P["final_norm"], head_w: P[head_w]}
        loss_sum, g_head, g = 0.0, None, []
        blk = min(HEAD_BLOCK, x.shape[1])
        for b in range(x.shape[0]):          # a block of one sequence at a time
            row = []
            for c in range(0, x.shape[1], blk):
                lb, gh, gx = self._head(hp, x[b:b + 1, c:c + blk],
                                        labels[b:b + 1, c:c + blk])
                loss_sum += float(lb)
                g_head = gh if g_head is None else jax.tree.map(jnp.add,
                                                                g_head, gh)
                row.append(gx)
            g.append(jnp.concatenate(row, axis=1))
        del x
        scale = 1.0 / n_tok
        gx = jnp.concatenate(g, axis=0) * scale
        g_head = {k: v * scale for k, v in g_head.items()}
        g_tied = g_head.pop("embed", None)   # a tied head's part of embed's
        sq = [jnp.sum(jnp.square(v)) for v in g_head.values()]
        host = {k: np.asarray(v) for k, v in g_head.items()}
        del g_head
        nb = layers.n_blocks(self.a)
        grads = [{} for _ in range(n_pos)]
        for layer in reversed(range(self.a["num_hidden_layers"])):
            pos, block = layer % n_pos, layer // n_pos
            gp, gx, s = self._bwd(P["layers"][pos], block, xs[layer], gx, pos)
            xs[layer] = None
            sq.append(s)
            for k, v in jax.device_get(gp).items():
                if k not in grads[pos]:
                    grads[pos][k] = np.empty((nb,) + v.shape, F32)
                grads[pos][k][block] = v
        ge = self._embed_grad(x_in.reshape(-1), gx.reshape(-1, gx.shape[-1]),
                              self.a["vocab_size"])
        if g_tied is not None:
            ge = ge + g_tied
        sq.append(jnp.sum(jnp.square(ge)))
        gnorm = math.sqrt(float(sum(float(v) for v in sq)))
        host["layers"] = grads
        host["embed"] = np.asarray(ge)
        self.grads = host
        self.scale = min(1.0, self.opt["clip_norm"] / (gnorm + 1e-9))
        return loss_sum / n_tok + aux_sum, gnorm

    def update(self, move: bool = True):
        """AdamW step k (= number of updates so far, plus one) with the last
        gradient, leaf by leaf. ``move=False`` plants a step that updates the
        moments and leaves the parameters as they were."""
        o = self.opt
        self.count += 1
        k = self.count
        lr = o["lr"] * min(k / max(o["warmup"], 1), 1.0)
        coef = jnp.asarray([lr, 1.0 - o["b1"] ** k, 1.0 - o["b2"] ** k,
                            self.scale], F32)
        is_moment = lambda x: isinstance(x, tuple)  # noqa: E731
        flat_p, tdef = jax.tree.flatten(self.params)
        flat_m = jax.tree.leaves(self.m, is_leaf=is_moment)
        flat_v = jax.tree.leaves(self.v, is_leaf=is_moment)
        flat_g = jax.tree.leaves(self.grads)
        out = [self._upd(p, g, m, v, coef, move)
               for p, g, m, v in zip(flat_p, flat_g, flat_m, flat_v)]
        self.params = jax.tree.unflatten(tdef, [x[0] for x in out])
        self.m = jax.tree.unflatten(tdef, [x[1] for x in out])
        self.v = jax.tree.unflatten(tdef, [x[2] for x in out])
        self.grads = {}

    def first_moment_norms(self) -> Dict[str, float]:
        """Per leaf, the norm of the gradient as the optimizer got it
        (clipped), worked out from the stored first moment after one step:
        ``|m| / (1 - b1)``."""
        return {k: float(v) / (1.0 - self.opt["b1"])
                for k, v in self._moment_norms(self.m).items()}

    def change_norms(self) -> Dict[str, float]:
        """Per leaf, the norm of the parameters' change since the seeded
        weights, as stored. The seeded weights are made again as
        ``__init__`` made them, op by op: inside ``jit`` the chip rounds
        some of them otherwise."""
        init = init_params(self.seed, self.a, self.pdtype)
        return {k: float(v) for k, v in diff_norms(self.params, init).items()}


@jax.jit
def diff_norms(params, init) -> Dict[str, jax.Array]:
    """Per leaf, the norm of ``params - init``."""
    init = flat_leaves(init)
    return {k: jnp.sqrt(jnp.sum(jnp.square(x.astype(F32) - init[k].astype(F32))))
            for k, x in flat_leaves(params).items()}


def reference_steps(a: dict, train: dict, opt: dict, seed: int, n: int,
                    precision: str = "f32", fault: str = "none") -> dict:
    """The first ``n`` training steps: each step's loss and gradient norm,
    the per-leaf gradient norms worked out from the state after one step
    (``grad_leaf``), and the per-leaf change of the parameters after the
    first update (``change1_leaf``) and after the last (``change_last_leaf``).

    ``fault`` plants a fault of the timed path in the reference put in its
    place: ``half_batch`` (the loss and gradient over half of the batch's
    sequences) or ``frozen`` (steps that leave the parameters as they were).
    """
    with jax.default_matmul_precision("highest"):
        ref = Reference(a, train, opt, seed, precision)
        bs = train["batch_size"]
        rows = slice(0, bs // 2) if fault == "half_batch" else slice(None)
        out = {"loss": [], "gnorm": []}
        for step in range(1, n + 1):
            toks = corpus_batch(seed, step, a["vocab_size"], train["seq_len"],
                                bs)
            loss, gn = ref.loss_and_grad(toks, rows)
            out["loss"].append(loss)
            out["gnorm"].append(gn)
            ref.update(move=fault != "frozen")
            if step == 1:
                out["grad_leaf"] = ref.first_moment_norms()
                out["change1_leaf"] = ref.change_norms()
        out["change_last_leaf"] = ref.change_norms()
        del ref
        gc.collect()             # free its device arrays before the caller's next
    return out

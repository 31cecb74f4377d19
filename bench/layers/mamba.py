"""The Mamba-1 selective-scan mixer (in_proj, causal depthwise conv, x_proj
to dt/B/C, dt_proj, the scan, the SiLU gate, out_proj), with no conv bias
and no norms on dt, B and C."""
import math

import jax
import jax.numpy as jnp

from bench.layers import normal

ROLE = "mixer"
SPEC = "mamba"
KEYS = ("intermediate_size", "state_size", "conv_kernel", "time_step_rank")
SUBKEY = 0
F32 = jnp.float32


def arch_fields(a: dict) -> dict:
    return {"family": "ssm", "subquadratic": True,
            "mamba": {"d_state": a["state_size"], "d_conv": a["conv_kernel"],
                      "expand": a["intermediate_size"] // a["hidden_size"],
                      "dt_rank": a["time_step_rank"]}}


def init(key, a: dict, dtype) -> dict:
    d, di, ds, dc, dr = (a["hidden_size"], a["intermediate_size"],
                         a["state_size"], a["conv_kernel"],
                         a["time_step_rank"])
    km = jax.random.split(key, 6)
    return {
        "in_proj": normal(km[0], (d, 2 * di), 1 / math.sqrt(d), dtype),
        "conv_w": normal(km[1], (dc, di), 1 / math.sqrt(dc), dtype),
        "x_proj": normal(km[2], (di, dr + 2 * ds), 1 / math.sqrt(di), dtype),
        "dt_proj": normal(km[3], (dr, di), 1 / math.sqrt(dr), dtype),
        "dt_bias": jnp.zeros((di,), F32) + jnp.log(jnp.expm1(0.01)),
        "A_log": jnp.log(jnp.broadcast_to(
            jnp.arange(1, ds + 1, dtype=F32), (di, ds))).astype(F32),
        "D": jnp.ones((di,), F32),
        "out_proj": normal(km[5], (di, d), 1 / math.sqrt(di), dtype)}


def forward(p, h, a, mm, chunk=256):
    B, S, _ = h.shape
    di, ds, dc, dr = (a["intermediate_size"], a["state_size"],
                      a["conv_kernel"], a["time_step_rank"])
    xz = mm("bsd,de->bse", h, p["in_proj"])
    u, z = xz[..., :di], xz[..., di:]
    up = jnp.concatenate([jnp.zeros((B, dc - 1, di), F32), u], axis=1)
    w = p["conv_w"].astype(F32)
    conv = sum(up[:, i:i + S] * w[i] for i in range(dc))  # causal depthwise
    u = jax.nn.silu(conv)
    dbc = mm("bsi,ie->bse", u, p["x_proj"])
    dt_r, Bc, Cc = dbc[..., :dr], dbc[..., dr:dr + ds], dbc[..., dr + ds:]
    dt = jax.nn.softplus(mm("bsr,ri->bsi", dt_r, p["dt_proj"])
                         + p["dt_bias"].astype(F32))
    A = -jnp.exp(p["A_log"].astype(F32))                    # [di, ds]

    def one(state, xs):                        # one time step, in order
        dt_t, u_t, b_t, c_t = xs               # [B,di] [B,di] [B,ds] [B,ds]
        state = (jnp.exp(dt_t[..., None] * A) * state
                 + (dt_t * u_t)[..., None] * b_t[:, None, :])
        return state, jnp.einsum("bin,bn->bi", state, c_t,
                                 precision=jax.lax.Precision.HIGHEST)

    @jax.checkpoint
    def chunk_steps(state, xs):                # keeps one state per chunk
        return jax.lax.scan(one, state, xs)

    chunk = min(chunk, S) if S % min(chunk, S) == 0 else S

    def t_major(t):                            # [B,S,...] -> [S/c, c, B, ...]
        t = jnp.moveaxis(t, 1, 0)
        return t.reshape((S // chunk, chunk) + t.shape[1:])

    _, y = jax.lax.scan(chunk_steps, jnp.zeros((B, di, ds), F32),
                        tuple(t_major(t) for t in (dt, u, Bc, Cc)))
    y = jnp.moveaxis(y.reshape((S, B, di)), 0, 1)
    y = (y + u * p["D"].astype(F32)) * jax.nn.silu(z)
    return mm("bsi,id->bsd", y, p["out_proj"]), None


def matmul_params(a: dict) -> int:
    d, di, ds, dr = (a["hidden_size"], a["intermediate_size"],
                     a["state_size"], a["time_step_rank"])
    return d * 2 * di + di * (dr + 2 * ds) + dr * di + di * d


def flops_fwd(a: dict, batch: int, seq: int) -> int:
    """Selective scan per token and channel x state: exp(dt*A) (1), dt*u*B
    (2), h = a*h + b (2), y += h*C (2); plus the depthwise conv (2 per
    tap)."""
    di, ds, dc = a["intermediate_size"], a["state_size"], a["conv_kernel"]
    return batch * seq * (7 * di * ds + 2 * dc * di)

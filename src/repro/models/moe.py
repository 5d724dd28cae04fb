"""Mixture-of-Experts layer: top-k routing, capacity dispatch, expert parallel.

Two execution paths with identical semantics (the single-device path is the
test oracle for the distributed one):

* ``mesh`` given — expert parallelism via ``shard_map``: experts shard over the
  "model" mesh axis; every model rank routes the (batch-sharded, model-
  replicated) token block to its local experts through a capacity-bounded
  scatter buffer, runs the expert FFNs locally, and the partial outputs are
  psum'd over "model".  The dispatch buffer is (E_local, C_local, D) — per
  data-shard capacity, so no tensor ever carries global token count × expert
  count (the classic GShard dispatch blow-up).

* ``mesh=None`` — reference: same routing math, experts applied via masked
  dense einsum (affordable at test scale).

Router aux losses (load-balance + z-loss) are returned alongside the output.
"""
from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as PS

from .config import ModelConfig
from .layers import P, mlp_spec, swiglu


def moe_spec(cfg: ModelConfig) -> Dict[str, P]:
    m, d = cfg.moe, cfg.d_model
    spec = {
        "router": P((d, m.num_experts), ("embed", None), scale=0.02,
                    dtype=jnp.float32),
        "w_gate": P((m.num_experts, d, m.d_ff_expert), ("exp", "embed", "ffn")),
        "w_up": P((m.num_experts, d, m.d_ff_expert), ("exp", "embed", "ffn")),
        "w_down": P((m.num_experts, m.d_ff_expert, d), ("exp", "ffn", "embed"),
                    scale=0.02 / 2),
    }
    if m.shared_ff:
        spec["shared"] = mlp_spec(d, m.shared_ff)
    return spec


def _route(router_w: jnp.ndarray, x: jnp.ndarray, k: int):
    """x (S,D) -> (weights (S,k), expert_idx (S,k), aux losses)."""
    logits = (x.astype(jnp.float32) @ router_w).astype(jnp.float32)  # (S,E)
    probs = jax.nn.softmax(logits, axis=-1)
    vals, idx = jax.lax.top_k(probs, k)
    vals = vals / jnp.maximum(vals.sum(-1, keepdims=True), 1e-9)
    # Switch-style load-balance loss + z-loss
    E = router_w.shape[1]
    me = probs.mean(axis=0)                                   # (E,)
    ce = jnp.zeros(E).at[idx.reshape(-1)].add(1.0) / max(idx.size, 1)
    lb = E * jnp.sum(me * ce)
    z = jnp.mean(jax.scipy.special.logsumexp(logits, axis=-1) ** 2)
    return vals, idx, lb, z


def _expert_ffn(buf: jnp.ndarray, wg, wu, wd) -> jnp.ndarray:
    """buf (E,C,D) -> (E,C,D), per-expert SwiGLU."""
    dt = buf.dtype
    g = jnp.einsum("ecd,edf->ecf", buf, wg.astype(dt))
    u = jnp.einsum("ecd,edf->ecf", buf, wu.astype(dt))
    return jnp.einsum("ecf,efd->ecd", jax.nn.silu(g) * u, wd.astype(dt))


def _dispatch_compute_combine(x_flat: jnp.ndarray, weights, idx, wg, wu, wd,
                              e_base: int, e_local: int, capacity: int):
    """Tokens (S,D) -> partial output from experts [e_base, e_base+e_local).

    Scatter tokens into an (E_local, C, D) buffer (capacity-dropping), run the
    expert FFNs, gather back weighted.  Pure local compute.
    """
    S, D = x_flat.shape
    k = idx.shape[1]
    eid = idx.reshape(-1) - e_base                            # (S*k,)
    w = weights.reshape(-1)
    local = (eid >= 0) & (eid < e_local)
    eid_c = jnp.clip(eid, 0, e_local - 1)
    # position of each assignment within its expert (stable, first-come)
    onehot = (eid_c[:, None] == jnp.arange(e_local)[None, :]) & local[:, None]
    pos = (jnp.cumsum(onehot.astype(jnp.int32), axis=0) - 1)
    pos = jnp.take_along_axis(pos, eid_c[:, None], axis=1)[:, 0]  # (S*k,)
    keep = local & (pos >= 0) & (pos < capacity)
    pos_c = jnp.clip(pos, 0, capacity - 1)
    tok = jnp.repeat(jnp.arange(S), k)
    upd = x_flat[tok] * keep[:, None].astype(x_flat.dtype)
    buf = jnp.zeros((e_local, capacity, D), x_flat.dtype)
    buf = buf.at[eid_c, pos_c].add(upd)
    out_buf = _expert_ffn(buf, wg, wu, wd)                    # (E_l, C, D)
    gathered = out_buf[eid_c, pos_c]                          # (S*k, D)
    gathered = gathered * (w * keep).astype(gathered.dtype)[:, None]
    return gathered.reshape(S, k, D).sum(axis=1)              # (S, D)


def moe_apply(p: Dict, x: jnp.ndarray, cfg: ModelConfig,
              mesh=None) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """x (B,T,D) -> (y (B,T,D), aux_loss scalar)."""
    m = cfg.moe
    B, T, D = x.shape

    if mesh is not None and "model" in mesh.axis_names:
        y, aux = _moe_shard_map(p, x, cfg, mesh)
    else:
        x_flat = x.reshape(-1, D)
        weights, idx, lb, z = _route(p["router"], x_flat, m.top_k)
        S = x_flat.shape[0]
        cap = max(int(m.top_k * S / m.num_experts * m.capacity_factor), 1)
        y = _dispatch_compute_combine(
            x_flat, weights, idx, p["w_gate"], p["w_up"], p["w_down"],
            0, m.num_experts, cap).reshape(B, T, D)
        aux = m.aux_coef * lb + m.router_z_coef * z
    if m.shared_ff:
        y = y + swiglu(x, **{k: p["shared"][k]
                             for k in ("w_gate", "w_up", "w_down")})
    return y, aux


def _moe_shard_map(p: Dict, x: jnp.ndarray, cfg: ModelConfig, mesh):
    m = cfg.moe
    B, T, D = x.shape
    batch_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    dp = 1
    for a in batch_axes:
        dp *= mesh.shape[a]
    ep = mesh.shape["model"]
    e_local = m.num_experts // ep
    assert e_local * ep == m.num_experts, \
        f"experts {m.num_experts} must divide model axis {ep}"
    S_local = (B // dp) * T
    cap = max(int(m.top_k * S_local / m.num_experts * m.capacity_factor), 1)

    def local_fn(xb, router, wg, wu, wd):
        # xb (B_l, T, D) — replicated over "model"; wg.. local expert slices
        xf = xb.reshape(-1, D)
        weights, idx, lb, z = _route(router, xf, m.top_k)
        e_base = jax.lax.axis_index("model") * e_local
        y_part = _dispatch_compute_combine(
            xf, weights, idx, wg, wu, wd, e_base, e_local, cap)
        y = jax.lax.psum(y_part, "model")
        aux = m.aux_coef * lb + m.router_z_coef * z
        # aux is identical across "model" ranks (routing sees the replicated
        # token block); mean over the batch axes only.
        aux = jax.lax.pmean(aux, batch_axes)
        return y.reshape(xb.shape), aux

    batch_part = batch_axes if len(batch_axes) > 1 else batch_axes[0]
    expert_spec = PS("model", None, None)
    y, aux = jax.shard_map(
        local_fn, mesh=mesh,
        in_specs=(PS(batch_part, None, None), PS(None, None),
                  expert_spec, expert_spec, expert_spec),
        out_specs=(PS(batch_part, None, None), PS()),
    )(x, p["router"], p["w_gate"], p["w_up"], p["w_down"])
    return y, aux

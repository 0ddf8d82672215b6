"""Capacity-based Mixture-of-Experts with expert parallelism (EP).

Distribution scheme (DESIGN.md §4): activations reach the FFN replicated
over the TP ("model") axis and sharded over the DP axes.  Each (data, model)
device therefore already holds its token shard, and we assign experts to the
"model" axis: device (d, m) runs experts [m·E/tp, (m+1)·E/tp) over data
shard d's tokens with a capacity-bounded gather, and a psum over "model"
reassembles the gate-weighted combine.  No all-to-all is needed — the psum
is the same collective TP would issue after a dense FFN.

Expert weights are additionally FSDP-sharded on d_model over the DP axes;
shard_map receives them sharded and all-gathers per layer (standard FSDP
unshard, transient full-layer copy in VMEM/HBM).

Token dropping: per-expert capacity C = ceil(T_local·top_k/E · cf).  The
oracle test checks equivalence to dense routing when C >= T_local.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models.layers import CDTYPE, dense_init


def init_moe(key, cfg):
    mc = cfg.moe
    d, E, f = cfg.d_model, mc.n_experts, mc.d_expert
    ks = jax.random.split(key, 4)
    p = {
        "router": dense_init(ks[0], d, E, scale=0.02),
        "wg": jax.random.normal(ks[1], (E, d, f), jnp.float32) / jnp.sqrt(d),
        "wu": jax.random.normal(ks[2], (E, d, f), jnp.float32) / jnp.sqrt(d),
        "wd": jax.random.normal(ks[3], (E, f, d), jnp.float32) / jnp.sqrt(f),
    }
    if mc.n_shared:
        k1, k2, k3 = jax.random.split(ks[0], 3)
        fs = mc.n_shared * f
        p["shared"] = {"wg": dense_init(k1, d, fs), "wu": dense_init(k2, d, fs),
                       "wd": dense_init(k3, fs, d)}
    return p


def _expert_compute(xg, wg, wu, wd):
    """xg (E, C, D) -> (E, C, D) through per-expert SwiGLU."""
    h = (jax.nn.silu(jnp.einsum("ecd,edf->ecf", xg, wg,
                                preferred_element_type=jnp.float32))
         * jnp.einsum("ecd,edf->ecf", xg, wu,
                      preferred_element_type=jnp.float32))
    return jnp.einsum("ecf,efd->ecd", h.astype(xg.dtype), wd,
                      preferred_element_type=jnp.float32)


def _route_and_compute(x_flat, router, wg, wu, wd, *, top_k, capacity,
                       e_offset, n_local):
    """Local MoE over T_local tokens and n_local experts.
    Returns (out (T, D) f32 partial sum, router probs (T, E) f32)."""
    T, D = x_flat.shape
    E = router.shape[1]
    logits = (x_flat.astype(jnp.float32) @ router.astype(jnp.float32))
    probs = jax.nn.softmax(logits, -1)                      # (T, E)
    gate_vals, gate_idx = jax.lax.top_k(probs, top_k)       # (T, k)
    # normalized top-k gates scattered back to (T, E)
    gmat = jnp.zeros((T, E), jnp.float32)
    gmat = gmat.at[jnp.arange(T)[:, None], gate_idx].set(
        gate_vals / jnp.maximum(gate_vals.sum(-1, keepdims=True), 1e-9))
    # local expert slice -> expert-choice top-C tokens
    loc = jax.lax.dynamic_slice_in_dim(gmat, e_offset, n_local, axis=1).T  # (El, T)
    score = jnp.where(loc > 0, loc, -jnp.inf)
    top_val, tok_idx = jax.lax.top_k(score, min(capacity, T))              # (El, C)
    alive = jnp.isfinite(top_val)
    gates = jnp.where(alive, top_val, 0.0)
    xg = x_flat[tok_idx.reshape(-1)].reshape(n_local, -1, D).astype(CDTYPE)
    y = _expert_compute(xg, wg.astype(CDTYPE), wu.astype(CDTYPE),
                        wd.astype(CDTYPE))                                  # (El,C,D) f32
    y = y * gates[..., None]
    out = jnp.zeros((T, D), jnp.float32)
    out = out.at[tok_idx.reshape(-1)].add(y.reshape(-1, D))
    return out, probs


def _aux_loss(probs, gmat_mean_assign=None):
    """Switch-style load-balance loss: E * sum_e mean(prob_e) * mean(assign_e).
    We use the soft version E * sum mean(prob)^2 which has the same optimum
    and avoids carrying assignments across shards."""
    me = probs.mean(0)
    return probs.shape[1] * jnp.sum(me * me)


def moe_forward(params, cfg, x, *, mesh=None, dp_axes=("data",),
                tp_axis="model", psum_dtype=None):
    """x (B, S, D) -> (out (B, S, D), aux_loss scalar).

    ``psum_dtype=bf16`` (or env REPRO_MOE_PSUM_BF16=1) compresses the EP
    combine collective — EXPERIMENTS.md §Perf cell B."""
    import os as _os
    if psum_dtype is None and _os.environ.get("REPRO_MOE_PSUM_BF16"):
        psum_dtype = jnp.bfloat16
    mc = cfg.moe
    B, S, D = x.shape
    E = mc.n_experts

    dp_size = 1
    if mesh is not None and tp_axis in getattr(mesh, "axis_names", ()):
        for a in dp_axes:
            dp_size *= mesh.shape[a]
    # fall back to the local (replicated) path when the batch cannot shard
    # over DP (e.g. batch=1 long-context decode) or experts don't divide TP
    unshardable = (mesh is None
                   or tp_axis not in getattr(mesh, "axis_names", ())
                   or B % dp_size != 0
                   or E % mesh.shape[tp_axis] != 0)

    if unshardable:
        x_flat = x.reshape(-1, D)
        T = x_flat.shape[0]
        if T <= 32:
            # DROPLESS decode path: tiny token counts must not compete for
            # expert capacity (a decode step's routing would otherwise depend
            # on unrelated requests in the batch).  Per-slot expert-weight
            # gather — T*top_k gathers of (D, F) weights.
            logits = x_flat.astype(jnp.float32) @ params["router"].astype(jnp.float32)
            probs = jax.nn.softmax(logits, -1)
            vals, idx = jax.lax.top_k(probs, mc.top_k)
            vals = vals / jnp.maximum(vals.sum(-1, keepdims=True), 1e-9)
            out = jnp.zeros((T, D), jnp.float32)
            xc = x_flat.astype(CDTYPE)
            for j in range(mc.top_k):
                wg = params["wg"][idx[:, j]].astype(CDTYPE)   # (T, D, F)
                wu = params["wu"][idx[:, j]].astype(CDTYPE)
                wd = params["wd"][idx[:, j]].astype(CDTYPE)
                h = (jax.nn.silu(jnp.einsum("td,tdf->tf", xc, wg))
                     * jnp.einsum("td,tdf->tf", xc, wu))
                y = jnp.einsum("tf,tfd->td", h, wd,
                               preferred_element_type=jnp.float32)
                out = out + vals[:, j, None] * y
            aux = _aux_loss(probs)
        else:
            cap = max(1, int(T * mc.top_k / E * mc.capacity_factor))
            out, probs = _route_and_compute(
                x_flat, params["router"], params["wg"], params["wu"],
                params["wd"], top_k=mc.top_k, capacity=cap, e_offset=0,
                n_local=E)
            aux = _aux_loss(probs)
        out = out.reshape(B, S, D)
    else:
        tp = mesh.shape[tp_axis]
        n_local = E // tp
        dp = 1
        for a in dp_axes:
            dp *= mesh.shape[a]
        t_local = (B // dp) * S
        cap = max(1, int(t_local * mc.top_k / E * mc.capacity_factor))

        def local_fn(xl, router, wg, wu, wd):
            # FSDP unshard of this layer's experts (all-gather over dp axes)
            for a in dp_axes:
                wg = jax.lax.all_gather(wg, a, axis=1, tiled=True)
                wu = jax.lax.all_gather(wu, a, axis=1, tiled=True)
                wd = jax.lax.all_gather(wd, a, axis=2, tiled=True)
            xf = xl.reshape(-1, D)
            m_idx = jax.lax.axis_index(tp_axis)
            out, probs = _route_and_compute(
                xf, router, wg, wu, wd, top_k=mc.top_k, capacity=cap,
                e_offset=m_idx * n_local, n_local=n_local)
            # gradient/activation compression: the EP combine is a sum of
            # <= top_k + shared bf16-computed contributions — psum in bf16
            # halves the TP collective bytes (EXPERIMENTS.md §Perf B)
            if psum_dtype is not None:
                out = jax.lax.psum(out.astype(psum_dtype), tp_axis)
            else:
                out = jax.lax.psum(out, tp_axis)
            aux = jax.lax.pmean(_aux_loss(probs), dp_axes)
            return out.reshape(xl.shape).astype(
                psum_dtype or out.dtype), aux

        local_fn_sm = jax.shard_map(
            local_fn, mesh=mesh,
            in_specs=(P(dp_axes, None, None), P(None, None),
                      P(tp_axis, dp_axes, None), P(tp_axis, dp_axes, None),
                      P(tp_axis, None, dp_axes)),
            out_specs=(P(dp_axes, None, None), P()),
            check_vma=False,
        )
        out, aux = local_fn_sm(x, params["router"], params["wg"],
                               params["wu"], params["wd"])

    out = out.astype(x.dtype)
    if mc.n_shared:
        sp = params["shared"]
        xc = x.astype(CDTYPE)
        h = jax.nn.silu(xc @ sp["wg"].astype(CDTYPE)) * (xc @ sp["wu"].astype(CDTYPE))
        out = out + (h @ sp["wd"].astype(CDTYPE)).astype(x.dtype)
    return out, aux * mc.aux_loss_weight

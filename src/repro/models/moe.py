"""Mixture-of-Experts layer with sort-based capacity dispatch.

Covers both assigned MoE archs on the same code path:
  * deepseek-moe-16b : 64 routed (top-6, fine-grained) + 2 shared experts,
                       E (64) >= model-axis (16)  -> expert-parallel slabs
  * grok-1-314b      : 8 routed (top-2), E (8) < model-axis (16)
                       -> experts x ff 2-D split (each expert's FFN is
                          sharded (model/E)-ways along d_ff)

Dispatch (DESIGN.md section 4): activations are replicated across the
model axis (batch is data-sharded), so routing + sort are computed
redundantly per model shard and each shard gathers ONLY the tokens of its
local expert slice into an (E_local, C, D) buffer — no all-to-all is
needed; the single combine psum over "model" (the same collective a
Megatron MLP needs anyway) merges expert outputs AND intra-expert ff
partial sums in one reduction.

Inside jit this runs as a nested shard_map over the full mesh.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.models import sharding as sh
from repro.models.config import ModelConfig

Array = jax.Array


def moe_decls(cfg: ModelConfig):
    m = cfg.moe
    d, dt = cfg.d_model, cfg.jnp_dtype
    E, F = m.n_experts, m.d_ff_expert
    decls = {
        "router": sh.dense((d, E), ("embed", None), jnp.float32),
        "w_gate": sh.dense((E, d, F), ("experts", "embed", "expert_ff"), dt),
        "w_up": sh.dense((E, d, F), ("experts", "embed", "expert_ff"), dt),
        "w_down": sh.dense((E, F, d), ("experts", "expert_ff", "embed"), dt,
                           fan_in=F),
    }
    if m.n_shared:
        Fs = m.d_ff_shared or m.n_shared * F
        decls["shared"] = {
            "w_gate": sh.dense((d, Fs), ("embed", "ff"), dt),
            "w_up": sh.dense((d, Fs), ("embed", "ff"), dt),
            "w_down": sh.dense((Fs, d), ("ff", "embed"), dt),
        }
    return decls


def apply_moe_dense(cfg: ModelConfig, params, x: Array) -> Array:
    """Gather-free MoE for tiny token counts (decode): computes ALL experts
    on all tokens and masks by the top-k gates.

    Rationale: a serving batch touches every expert anyway, so the weight
    READ traffic is identical to sparse dispatch, while the shard_map
    dispatch path would all-gather the FSDP-sharded expert weights every
    step (measured 77 GB/step on grok decode_32k). Here the einsums consume
    the sharded weights in place — GSPMD reduces small activation partials
    instead of moving weights. Extra flops (E/top_k) are irrelevant at
    decode: the step is bandwidth-bound.
    """
    m = cfg.moe
    E, K = m.n_experts, m.top_k
    B, S, D = x.shape
    logits = jnp.einsum("bsd,de->bse", x.astype(jnp.float32),
                        params["router"])
    gates_all = jax.nn.softmax(logits, axis=-1)
    top_g, _ = jax.lax.top_k(gates_all, K)
    thresh = top_g[..., -1:]
    weights = jnp.where(gates_all >= thresh, gates_all, 0.0)
    weights = weights / jnp.maximum(weights.sum(-1, keepdims=True), 1e-9)

    hg = jnp.einsum("bsd,edf->besf", x, params["w_gate"],
                    preferred_element_type=jnp.float32)
    hu = jnp.einsum("bsd,edf->besf", x, params["w_up"],
                    preferred_element_type=jnp.float32)
    h = (jax.nn.silu(hg) * hu).astype(x.dtype)
    out_e = jnp.einsum("besf,efd->besd", h, params["w_down"],
                       preferred_element_type=jnp.float32)
    out = jnp.einsum("besd,bse->bsd", out_e, weights).astype(x.dtype)
    if m.n_shared:
        sp = params["shared"]
        hs = jax.nn.silu(x @ sp["w_gate"]) * (x @ sp["w_up"])
        out = out + hs @ sp["w_down"]
    return out


def apply_moe(cfg: ModelConfig, params, x: Array, mesh: Mesh,
              rules: sh.ShardingRules):
    """x: (B, S, D) -> (B, S, D). Routed experts + optional shared experts."""
    m = cfg.moe
    Bsz, S, D = x.shape
    E, K, F = m.n_experts, m.top_k, m.d_ff_expert

    dspec = tuple(a for a in ("pod", "data") if a in mesh.shape)
    dspec = dspec if len(dspec) > 1 else (dspec[0] if dspec else None)
    model_ax = "model" if "model" in mesh.shape else None

    decls = moe_decls(cfg)
    w_specs = {k: sh.resolve_spec(params[k].shape, decls[k].logical_axes,
                                  rules, mesh)
               for k in ("router", "w_gate", "w_up", "w_down")}

    x_spec = P(dspec, None, None)

    local = functools.partial(
        _moe_local, cfg=cfg, mesh=mesh, w_specs=w_specs, model_ax=model_ax)

    mapped = jax.shard_map(
        local, mesh=mesh,
        in_specs=(x_spec, w_specs["router"], w_specs["w_gate"],
                  w_specs["w_up"], w_specs["w_down"]),
        out_specs=x_spec, check_vma=False,
    )
    out = mapped(x, params["router"], params["w_gate"], params["w_up"],
                 params["w_down"])

    if m.n_shared:
        sp = params["shared"]
        h = jax.nn.silu(x @ sp["w_gate"]) * (x @ sp["w_up"])
        out = out + h @ sp["w_down"]
    return out


def _moe_local(x_l, wr, wg, wu, wd, *, cfg: ModelConfig, mesh: Mesh,
               w_specs, model_ax):
    """Per-shard body. x_l: (B_l, S, D) local tokens (replicated over model);
    w*: local expert-weight blocks per w_specs."""
    m = cfg.moe
    E, K = m.n_experts, m.top_k
    B_l, S, D = x_l.shape
    T = B_l * S
    xt = x_l.reshape(T, D)

    n_model = mesh.shape.get("model", 1) if model_ax else 1
    m_idx = jax.lax.axis_index(model_ax) if model_ax else 0

    # FSDP all-gather of any data-sharded weight dim
    def fsdp_gather(w, spec, dim):
        ax = spec[dim] if len(spec) > dim else None
        if ax is None:
            return w
        axes = ax if isinstance(ax, tuple) else (ax,)
        for a in axes:
            if a != "model":
                w = jax.lax.all_gather(w, a, axis=dim, tiled=True)
        return w

    wr = fsdp_gather(wr, w_specs["router"], 0)

    # --- routing (identical on every model shard) -------------------------
    logits = (xt.astype(jnp.float32) @ wr.astype(jnp.float32))  # (T, E)
    gates_all = jax.nn.softmax(logits, axis=-1)
    gates, ids = jax.lax.top_k(gates_all, K)                    # (T, K)
    gates = gates / jnp.maximum(jnp.sum(gates, -1, keepdims=True), 1e-9)

    cap = int(m.capacity_factor * T * K / E)
    cap = max(8, -(-cap // 8) * 8)

    flat_e = ids.reshape(-1)
    flat_t = jnp.repeat(jnp.arange(T), K)
    flat_g = gates.reshape(-1).astype(x_l.dtype)
    order = jnp.argsort(flat_e, stable=True)
    se, st, sg = flat_e[order], flat_t[order], flat_g[order]
    starts = jnp.searchsorted(se, jnp.arange(E))
    pos = jnp.arange(T * K) - starts[se]
    keep = pos < cap

    # --- local expert slice ------------------------------------------------
    ge_spec = w_specs["w_gate"]
    e_sharded = len(ge_spec) > 0 and ge_spec[0] == "model"
    f_sharded = len(ge_spec) > 2 and ge_spec[2] == "model"
    if e_sharded:
        # case A (E % n_model == 0, e.g. deepseek 64 on 16): expert-
        # parallel — this shard holds E_l whole experts.
        E_l = E // n_model
        e_lo = m_idx * E_l
        redundancy = 1
        wg_l, wu_l, wd_l = wg, wu, wd   # already (E_l, ., .)
    elif f_sharded and n_model > 1:
        # case B (E < n_model, e.g. grok 8 on 16): every shard keeps ALL
        # experts but only a d_ff slice; silu(gate)*up is elementwise in
        # d_ff and w_down contracts over it, so each shard's output is a
        # partial sum that the combine psum below completes. No slicing,
        # no redundancy — total flops match the E-parallel case.
        E_l, e_lo = E, 0
        redundancy = 1
        wg_l, wu_l, wd_l = wg, wu, wd   # (E, ., F_l) blocks
    else:                               # fallback: replicated experts
        E_l, e_lo = E, 0
        redundancy = n_model
        wg_l, wu_l, wd_l = wg, wu, wd

    wg_l = fsdp_gather(wg_l, w_specs["w_gate"], 1)
    wu_l = fsdp_gather(wu_l, w_specs["w_up"], 1)
    wd_l = fsdp_gather(wd_l, w_specs["w_down"], 2)

    # --- gather local tokens into (E_l, cap, D) ----------------------------
    loc = se - e_lo
    in_local = (loc >= 0) & (loc < E_l) & keep
    idx_e = jnp.where(in_local, loc, E_l)       # OOB row -> dropped
    idx_c = jnp.where(in_local, pos, cap)
    buf = jnp.zeros((E_l, cap, D), x_l.dtype)
    buf = buf.at[idx_e, idx_c].set(xt[st], mode="drop")

    # --- expert FFN (gated) -------------------------------------------------
    h_g = jnp.einsum("ecd,edf->ecf", buf, wg_l)
    h_u = jnp.einsum("ecd,edf->ecf", buf, wu_l)
    h = jax.nn.silu(h_g) * h_u
    out_e = jnp.einsum("ecf,efd->ecd", h, wd_l)   # partial over f if split

    # --- combine: scatter back + ONE psum over model ------------------------
    vals = out_e[idx_e.clip(0, E_l - 1), idx_c.clip(0, cap - 1)]
    vals = vals * (sg * in_local.astype(sg.dtype))[:, None]
    out = jnp.zeros((T, D), x_l.dtype).at[st].add(vals)
    if model_ax:
        out = jax.lax.psum(out, model_ax)
    if redundancy > 1:
        out = out / redundancy
    return out.reshape(B_l, S, D)

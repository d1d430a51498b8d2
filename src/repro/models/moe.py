"""Mixture-of-experts FFN with top-k routing.

Two dispatch implementations:

  * ``sort``   — production path: flat (token, choice) pairs are sorted by
    expert id, ranked within each expert, and scattered into a dense
    (E, capacity, d) buffer.  FLOP cost is just the expert matmuls (honest
    roofline); shards under GSPMD with the expert axis on the mesh.
  * ``einsum`` — GShard-style one-hot dispatch, O(T·E·C·d) extra FLOPs;
    kept as a small-scale cross-check oracle for the sort path.

Arctic's dense residual branch and Kimi-K2-style shared experts are computed
alongside the routed experts.  A switch-style load-balance auxiliary loss is
returned so the trainer can add it.
"""
from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from .common import ModelConfig
from .layers import init_dense, swiglu
from .mlp import init_swiglu, swiglu_mlp


def init_moe(key, cfg: ModelConfig, dtype=None) -> Dict[str, Any]:
    dt = dtype or cfg.dtype
    d, f, E = cfg.d_model, cfg.d_ff, cfg.n_experts
    ks = jax.random.split(key, 6)
    p: Dict[str, Any] = {
        "router": init_dense(ks[0], d, E, jnp.float32),
        # stacked experts: (E, d, f) / (E, f, d)
        "w_gate": (jax.random.normal(ks[1], (E, d, f), jnp.float32)
                   / math.sqrt(d)).astype(dt),
        "w_up": (jax.random.normal(ks[2], (E, d, f), jnp.float32)
                 / math.sqrt(d)).astype(dt),
        "w_down": (jax.random.normal(ks[3], (E, f, d), jnp.float32)
                   / math.sqrt(f)).astype(dt),
    }
    if cfg.shared_expert_ff:
        p["shared"] = init_swiglu(ks[4], d, cfg.shared_expert_ff, dt)
    if cfg.dense_residual_ff:
        p["dense_residual"] = init_swiglu(ks[5], d, cfg.dense_residual_ff, dt)
    return p


def _capacity(n_tokens: int, cfg: ModelConfig) -> int:
    c = int(math.ceil(n_tokens * cfg.top_k / cfg.n_experts * cfg.capacity_factor))
    return max(cfg.top_k, c)


def _route(p, xf: jax.Array, cfg: ModelConfig):
    """xf (T, d) -> (topv, topi, aux_loss)."""
    logits = xf.astype(jnp.float32) @ p["router"]          # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    topv, topi = jax.lax.top_k(probs, cfg.top_k)           # (T, k)
    topv = topv / jnp.maximum(topv.sum(-1, keepdims=True), 1e-9)
    # switch-style load-balance loss
    E = cfg.n_experts
    frac_tokens = jnp.mean(
        jax.nn.one_hot(topi, E, dtype=jnp.float32).sum(1), axis=0)  # (E,)
    frac_probs = probs.mean(0)
    aux = E * jnp.sum(frac_tokens * frac_probs) / cfg.top_k
    return topv, topi, aux


def _experts(p, h: jax.Array) -> jax.Array:
    """h (E, C, d) -> (E, C, d) through each expert's SwiGLU."""
    g = jnp.einsum("ecd,edf->ecf", h, p["w_gate"])
    u = jnp.einsum("ecd,edf->ecf", h, p["w_up"])
    return jnp.einsum("ecf,efd->ecd", swiglu(g, u), p["w_down"])


def _moe_sort(p, xf: jax.Array, cfg: ModelConfig) -> Tuple[jax.Array, jax.Array]:
    T, d = xf.shape
    E, k = cfg.n_experts, cfg.top_k
    C = _capacity(T, cfg)
    topv, topi, aux = _route(p, xf, cfg)

    flat_e = topi.reshape(-1)                                 # (T*k,)
    flat_w = topv.reshape(-1)
    flat_t = jnp.repeat(jnp.arange(T), k)
    order = jnp.argsort(flat_e, stable=True)
    se, st, sw = flat_e[order], flat_t[order], flat_w[order]
    counts = jnp.bincount(se, length=E)
    starts = jnp.cumsum(counts) - counts
    rank = jnp.arange(T * k) - starts[se]
    keep = rank < C
    slot = jnp.where(keep, se * C + rank, E * C)              # E*C = drop bin

    buf = jnp.zeros((E * C + 1, d), xf.dtype).at[slot].set(xf[st])
    y = _experts(p, buf[: E * C].reshape(E, C, d)).reshape(E * C, d)
    contrib = jnp.where(keep[:, None],
                        y[jnp.where(keep, slot, 0)], 0.0) * sw[:, None].astype(xf.dtype)
    out = jnp.zeros((T, d), xf.dtype).at[st].add(contrib)
    return out, aux


def _moe_einsum(p, xf: jax.Array, cfg: ModelConfig) -> Tuple[jax.Array, jax.Array]:
    """GShard one-hot dispatch (oracle for small shapes)."""
    T, d = xf.shape
    E, k = cfg.n_experts, cfg.top_k
    C = _capacity(T, cfg)
    topv, topi, aux = _route(p, xf, cfg)

    # position of each (t, choice) within its expert, in (t, choice) order —
    # identical ordering to the stable sort of the sort path.
    choice_e = jax.nn.one_hot(topi, E, dtype=jnp.int32)       # (T, k, E)
    flat = choice_e.reshape(T * k, E)
    rank = jnp.cumsum(flat, axis=0) - flat                    # (T*k, E)
    rank = (rank * flat).sum(-1).reshape(T, k)
    keep = rank < C
    disp = (jax.nn.one_hot(topi, E, dtype=xf.dtype)[..., None]
            * jax.nn.one_hot(jnp.where(keep, rank, C), C + 1,
                             dtype=xf.dtype)[:, :, None, :])  # (T,k,E,C+1)
    disp = disp[..., :C]
    h = jnp.einsum("tkec,td->ecd", disp, xf)
    y = _experts(p, h)
    comb = (disp * topv[:, :, None, None].astype(xf.dtype))
    out = jnp.einsum("tkec,ecd->td", comb, y)
    return out, aux


def _moe_grouped(p, x: jax.Array, cfg: ModelConfig) -> Tuple[jax.Array, jax.Array]:
    """Batched grouped dispatch — the §Perf-optimized path.

    Key difference vs the vmap'd sort path: the dispatch buffer carries an
    explicit leading group dim and stays **data-sharded, expert-replicated**
    (anchored with a sharding constraint), so the scatter of group-local
    tokens is entirely local — GSPMD never emits the (G,E,C,d) buffer
    all-reduce across the model axis that dominates the baseline's
    collective roofline term.  The expert einsum then contracts against
    expert-sharded weights, which slices the replicated buffer locally.
    """
    G, T, d = x.shape
    E, k = cfg.n_experts, cfg.top_k
    C = _capacity(T, cfg)

    xf32 = x.reshape(G * T, d)
    topv, topi, aux = _route(p, xf32, cfg)
    topv = topv.reshape(G, T, k)
    topi = topi.reshape(G, T, k)

    flat_e = topi.reshape(G, T * k)
    flat_w = topv.reshape(G, T * k)
    flat_t = jnp.broadcast_to(jnp.repeat(jnp.arange(T), k)[None], (G, T * k))
    order = jnp.argsort(flat_e, axis=1, stable=True)
    se = jnp.take_along_axis(flat_e, order, 1)
    st = jnp.take_along_axis(flat_t, order, 1)
    sw = jnp.take_along_axis(flat_w, order, 1)
    # rank within expert: position minus the expert's start offset
    starts = jax.vmap(lambda row: jnp.searchsorted(row, jnp.arange(E)))(se)
    rank = jnp.arange(T * k)[None] - jnp.take_along_axis(starts, se, 1)
    keep = rank < C
    slot = jnp.where(keep, se * C + rank, 0)           # dropped -> slot 0,
    gathered = jnp.take_along_axis(x, st[..., None], 1)  # (G, T*k, d)
    vals = jnp.where(keep[..., None], gathered, 0.0)   # ... with zero value

    from .flags import constrain_batch_only
    buf = jnp.zeros((G, E * C, d), x.dtype)
    buf = buf.at[jnp.arange(G)[:, None], slot].add(vals)
    buf = constrain_batch_only(buf)                    # data-sharded only
    y = jax.vmap(lambda h: _experts(p, h.reshape(E, C, d)))(buf)
    y = constrain_batch_only(y.reshape(G, E * C, d))

    picked = jnp.take_along_axis(y, slot[..., None], 1)
    contrib = jnp.where(keep[..., None], picked, 0.0) * sw[..., None].astype(x.dtype)
    out = jnp.zeros((G, T, d), x.dtype).at[
        jnp.arange(G)[:, None], st].add(contrib)
    return out, aux


def _moe_shmap(p, x: jax.Array, cfg: ModelConfig, mesh,
               bt_axes) -> Tuple[jax.Array, jax.Array]:
    """Explicit expert-parallel MoE under shard_map — the §Perf winner.

    Every device holds E/model_size experts and its data-shard of token
    groups.  Routing, dispatch scatter, expert matmuls and the combine
    scatter are all LOCAL; the only collective is one psum of the (G,T,d)
    partial outputs over the model axis — volume ~= tokens x d, a factor
    k x capacity_factor smaller than the dispatch-buffer all-reduce GSPMD
    derives for the baseline mapping.
    """
    from jax.sharding import PartitionSpec as P

    G, T, d = x.shape
    E, k = cfg.n_experts, cfg.top_k
    C = _capacity(T, cfg)
    n_model = mesh.shape["model"]
    E_loc = E // n_model

    def local(p_loc, x_loc):
        g_loc = x_loc.shape[0]
        xf = x_loc.reshape(g_loc * T, d)
        topv, topi, aux = _route(p_loc, xf, cfg)
        aux = jax.lax.pmean(aux, bt_axes) if bt_axes else aux
        topv = topv.reshape(g_loc, T, k)
        topi = topi.reshape(g_loc, T, k)

        flat_e = topi.reshape(g_loc, T * k)
        flat_w = topv.reshape(g_loc, T * k)
        flat_t = jnp.broadcast_to(jnp.repeat(jnp.arange(T), k)[None],
                                  (g_loc, T * k))
        order = jnp.argsort(flat_e, axis=1, stable=True)
        se = jnp.take_along_axis(flat_e, order, 1)
        st = jnp.take_along_axis(flat_t, order, 1)
        sw = jnp.take_along_axis(flat_w, order, 1)
        starts = jax.vmap(lambda row: jnp.searchsorted(row, jnp.arange(E)))(se)
        rank = jnp.arange(T * k)[None] - jnp.take_along_axis(starts, se, 1)

        my = jax.lax.axis_index("model")
        off = my * E_loc
        keep = (rank < C) & (se >= off) & (se < off + E_loc)
        slot = jnp.where(keep, (se - off) * C + rank, 0)
        gathered = jnp.take_along_axis(x_loc, st[..., None], 1)
        vals = jnp.where(keep[..., None], gathered, 0.0)

        buf = jnp.zeros((g_loc, E_loc * C, d), x_loc.dtype)
        buf = buf.at[jnp.arange(g_loc)[:, None], slot].add(vals)
        y = jax.vmap(
            lambda h: _experts(p_loc, h.reshape(E_loc, C, d)))(buf)
        y = y.reshape(g_loc, E_loc * C, d)
        picked = jnp.take_along_axis(y, slot[..., None], 1)
        contrib = jnp.where(keep[..., None], picked,
                            0.0) * sw[..., None].astype(x_loc.dtype)
        out = jnp.zeros((g_loc, T, d), x_loc.dtype).at[
            jnp.arange(g_loc)[:, None], st].add(contrib)
        out = jax.lax.psum(out, "model")
        return out, aux

    x_spec = P(bt_axes if bt_axes else None, None, None)
    # only the routed-expert params enter the shard_map; shared experts /
    # dense residual branches are computed by the caller
    routed = {key: p[key] for key in ("router", "w_gate", "w_up", "w_down")}
    routed_specs = {key: (P("model", None, None)
                          if key != "router" else P()) for key in routed}
    out, aux = jax.shard_map(local, mesh=mesh,
                             in_specs=(routed_specs, x_spec),
                             out_specs=(x_spec, P()),
                             check_vma=False)(routed, x)
    return out, aux


def _moe_ep(p, x: jax.Array, cfg: ModelConfig, mesh,
            bt_axes) -> Tuple[jax.Array, jax.Array]:
    """Expert parallelism over the searched ``"expert"`` mesh axis — the
    runtime for a plan's ``ep_degree`` (plan format v5).

    Each expert rank owns ``E / ep`` experts (weights sharded on the mesh)
    and a batch shard of token groups.  Tokens route locally against the
    replicated router, the per-group dispatch buffer is built locally in
    global expert order, and one **all-to-all** per direction moves each
    expert's capacity slab to its owner (dispatch) and the expert outputs
    back (combine) — the collective the cost model prices for EP.  Group
    semantics (per-group capacity, stable-sort ranking, drop order) are
    identical to the single-device sort path, so outputs are
    token-identical to ``dispatch="sort"`` (tests/test_moe.py certifies
    this on an 8-fake-device mesh).
    """
    from jax.sharding import PartitionSpec as P

    G, T, d = x.shape
    E, k = cfg.n_experts, cfg.top_k
    C = _capacity(T, cfg)
    n_ep = mesh.shape["expert"]
    E_loc = E // n_ep
    bt = tuple(a for a in (bt_axes or ()) if a != "expert")
    aux_axes = bt + ("expert",)

    def local(p_loc, x_loc):
        g_loc = x_loc.shape[0]
        # route per group (aux is a per-group mean, like the single-device
        # path: joint routing over g_loc groups would skew the balance loss)
        topv, topi, aux = jax.vmap(
            lambda g: _route(p_loc, g, cfg))(x_loc)       # (g_loc, T, k)
        aux = jax.lax.pmean(aux.mean(), aux_axes)

        # per-group dispatch in GLOBAL expert order (same arithmetic as
        # _moe_grouped: stable sort, searchsorted starts, capacity drop)
        flat_e = topi.reshape(g_loc, T * k)
        flat_w = topv.reshape(g_loc, T * k)
        flat_t = jnp.broadcast_to(jnp.repeat(jnp.arange(T), k)[None],
                                  (g_loc, T * k))
        order = jnp.argsort(flat_e, axis=1, stable=True)
        se = jnp.take_along_axis(flat_e, order, 1)
        st = jnp.take_along_axis(flat_t, order, 1)
        sw = jnp.take_along_axis(flat_w, order, 1)
        starts = jax.vmap(
            lambda row: jnp.searchsorted(row, jnp.arange(E)))(se)
        rank = jnp.arange(T * k)[None] - jnp.take_along_axis(starts, se, 1)
        keep = rank < C
        slot = jnp.where(keep, se * C + rank, 0)
        gathered = jnp.take_along_axis(x_loc, st[..., None], 1)
        vals = jnp.where(keep[..., None], gathered, 0.0)
        buf = jnp.zeros((g_loc, E * C, d), x_loc.dtype)
        buf = buf.at[jnp.arange(g_loc)[:, None], slot].add(vals)

        # dispatch: slab for expert block q travels to rank q; combine
        # reverses the route.  tiled all-to-all keeps ranks' slabs in
        # global rank order, so the reshape below restores e*C + r slots.
        recv = jax.lax.all_to_all(buf, "expert", split_axis=1,
                                  concat_axis=0, tiled=True)
        y = jax.vmap(
            lambda h: _experts(p_loc, h.reshape(E_loc, C, d)))(recv)
        y = y.reshape(n_ep * g_loc, E_loc * C, d)
        y = jax.lax.all_to_all(y, "expert", split_axis=0,
                               concat_axis=1, tiled=True)  # (g_loc, E*C, d)

        picked = jnp.take_along_axis(y, slot[..., None], 1)
        contrib = jnp.where(keep[..., None], picked,
                            0.0) * sw[..., None].astype(x_loc.dtype)
        out = jnp.zeros((g_loc, T, d), x_loc.dtype).at[
            jnp.arange(g_loc)[:, None], st].add(contrib)
        return out, aux

    x_spec = P(bt + ("expert",), None, None)
    routed = {key: p[key] for key in ("router", "w_gate", "w_up", "w_down")}
    routed_specs = {key: (P("expert", None, None)
                          if key != "router" else P()) for key in routed}
    out, aux = jax.shard_map(local, mesh=mesh,
                             in_specs=(routed_specs, x_spec),
                             out_specs=(x_spec, P()),
                             check_vma=False)(routed, x)
    return out, aux


def expert_axis_usable(cfg: ModelConfig, mesh, batch: int,
                       bt_axes) -> bool:
    """Can ``_moe_ep`` run: an ``"expert"`` mesh axis of size > 1 exists,
    it divides the expert count, and the batch shards evenly over the
    data x expert axes."""
    if mesh is None or "expert" not in mesh.axis_names:
        return False
    n_ep = mesh.shape["expert"]
    if n_ep <= 1 or cfg.n_experts % n_ep:
        return False
    span = n_ep
    for a in (bt_axes or ()):
        if a != "expert":
            span *= mesh.shape[a]
    return batch % span == 0


def moe_ffn(p, x: jax.Array, cfg: ModelConfig, *,
            dispatch: str = "sort") -> Tuple[jax.Array, jax.Array]:
    """x (B, S, d) -> (out, aux_loss).

    Dispatch is *grouped* per batch row (GShard-style groups): tokens only
    compete for expert capacity within their own group, so the dispatch
    buffers carry a leading batch dimension that shards over the data mesh
    axis while the expert dimension shards over the model axis.

    When the ambient mesh carries an ``"expert"`` axis (a plan with
    ``ep_degree > 1``, see launch/mesh.py), the sort dispatch executes
    expert-parallel via :func:`_moe_ep` — sharded expert weights plus
    all-to-all dispatch/combine — regardless of ``cfg.moe_dispatch``.
    """
    B, S, d = x.shape
    from .flags import current_batch_axes, current_mesh
    ep_mesh = current_mesh()
    ep_bt = current_batch_axes()
    if (dispatch in ("sort", "grouped", "shmap")
            and expert_axis_usable(cfg, ep_mesh, B, ep_bt)):
        out, aux = _moe_ep(p, x, cfg, ep_mesh, ep_bt)
        if "shared" in p:
            out = out + swiglu_mlp(p["shared"], x)
        if "dense_residual" in p:
            out = out + swiglu_mlp(p["dense_residual"], x)
        return out, aux
    if dispatch == "sort" and cfg.moe_dispatch in ("grouped", "shmap"):
        dispatch = cfg.moe_dispatch
    if dispatch == "shmap":
        from .flags import current_batch_axes, current_mesh
        mesh = current_mesh()
        bt = current_batch_axes()
        ok = (mesh is not None and "model" in mesh.axis_names
              and cfg.n_experts % mesh.shape["model"] == 0
              and (not bt or B % max(1, __import__("math").prod(
                  mesh.shape[a] for a in bt)) == 0))
        if ok:
            out, aux = _moe_shmap(p, x, cfg, mesh, bt)
        else:   # fall back (no mesh context / indivisible shapes)
            out, aux = _moe_grouped(p, x, cfg)
    elif dispatch == "grouped":
        out, aux = _moe_grouped(p, x, cfg)
    else:
        fn = _moe_sort if dispatch == "sort" else _moe_einsum
        out, aux = jax.vmap(lambda xg: fn(p, xg, cfg))(x)
        aux = aux.mean()
    if "shared" in p:
        out = out + swiglu_mlp(p["shared"], x)
    if "dense_residual" in p:
        out = out + swiglu_mlp(p["dense_residual"], x)
    return out, aux

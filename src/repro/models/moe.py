"""Mixture-of-Experts with **sort-based token dispatch** — the paper's
robust sorting integrated in the training hot path.

Token routing produces n keys drawn from E ≤ 64 distinct values: exactly
the paper's DeterDupl instance.  Dispatch = sort items by expert id with
position tie-breaking (the RAMS/SSSS partition with *exact* splitters —
expert ownership boundaries — so no sampling phase is needed), exchange
with one fused slotted all-to-all, compute, and route back.  Load balance
of the static slots is the tie-breaking property of App. G; overflowed
items are dropped against a capacity factor, exactly like production MoE.

Two parallel layouts (DESIGN.md §5):
  * ``ep``  — experts sharded over the model axis (granite: 32/16): tokens
    are sequence-sharded over the axis and exchanged with the slotted
    all-to-all inside shard_map — the *distributed* sort path;
  * ``tp``  — experts replicated, FFN hidden dim TP-sharded (mixtral:
    8 experts on 16 ranks): grouping happens locally (the same one-hot
    scan the kway kernel implements), GSPMD reduces the down-projection.

``impl="dense"`` keeps the one-hot einsum dispatch as the measurable
baseline (benchmarks/moe_dispatch.py).
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from .layers import rms_norm


def init_moe(key, d: int, f: int, n_experts: int, dtype) -> dict:
    kr, ku, kg, kd = jax.random.split(key, 4)
    s_in = float(1.0 / np.sqrt(d))
    s_out = float(1.0 / np.sqrt(f))
    return {
        "router": jax.random.normal(kr, (d, n_experts), jnp.float32) * s_in,
        "up": jax.random.normal(ku, (n_experts, d, f), dtype) * s_in,
        "gate": jax.random.normal(kg, (n_experts, d, f), dtype) * s_in,
        "down": jax.random.normal(kd, (n_experts, f, d), dtype) * s_out,
    }


def _router(x, w, top_k: int):
    """x: (..., D) → (probs (..., k) f32, ids (..., k) i32, aux loss)."""
    logits = (x.astype(jnp.float32) @ w)
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_i = jax.lax.top_k(probs, top_k)
    top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
    # load-balancing aux loss (Switch): E · Σ_e f_e · p_e
    E = w.shape[1]
    me = jnp.mean(probs.reshape(-1, E), axis=0)
    fr = jnp.mean((top_i[..., None] == jnp.arange(E)).reshape(-1, E)
                  .astype(jnp.float32), axis=0)
    aux = E * jnp.sum(me * fr)
    return top_p, top_i.astype(jnp.int32), aux


def _expert_ffn(buf, up, gate, down):
    """buf: (E, C, D); weights (E, D, F)/(E, F, D)."""
    h = jnp.einsum("ecd,edf->ecf", buf, up)
    g = jnp.einsum("ecd,edf->ecf", buf, gate)
    h = jax.nn.silu(g) * h
    return jnp.einsum("ecf,efd->ecd", h, down)


def _group_by_expert(eids, n_experts: int, capacity: int):
    """One-hot scan grouping (the kway-kernel operation, jnp form).

    eids: (N,) int32 → (slot (N,), kept (N,) bool).  Slot is the position
    of the item within its expert's capacity buffer.
    """
    onehot = eids[:, None] == jnp.arange(n_experts, dtype=jnp.int32)[None, :]
    pos = jnp.cumsum(onehot, axis=0) - 1
    slot = jnp.sum(jnp.where(onehot, pos, 0), axis=1)
    kept = slot < capacity
    return slot, kept


def moe_local(x, p, cfg, *, capacity_factor: float = 2.0):
    """TP layout: group locally per batch row, einsum over all experts."""
    B, S, D = x.shape
    E, k = cfg.n_experts, cfg.top_k
    w, ids, aux = _router(x, p["router"], k)          # (B,S,k)
    N = S * k
    cap = int(capacity_factor * N / E) + 1
    ids2 = ids.reshape(B, N)
    w2 = w.reshape(B, N)

    slot, kept = jax.vmap(lambda e: _group_by_expert(e, E, cap))(ids2)
    # scatter tokens into (B, E, cap, D)
    xrep = jnp.repeat(x, k, axis=1).reshape(B, N, D)   # item i ← token i//k
    flat = jnp.where(kept, ids2 * cap + slot, E * cap)
    buf = jnp.zeros((B, E * cap + 1, D), x.dtype)
    buf = jax.vmap(lambda b, f, v: b.at[f].set(v))(buf, flat, xrep)
    buf = buf[:, :-1].reshape(B * E, cap, D).reshape(B, E, cap, D)
    out = jax.vmap(lambda bb: _expert_ffn(bb, p["up"], p["gate"], p["down"]))(buf)
    out = out.reshape(B, E * cap, D)
    # gather back
    gathered = jax.vmap(lambda o, f: o[jnp.clip(f, 0, E * cap - 1)])(out, flat)
    gathered = jnp.where(kept[..., None], gathered, 0.0)
    y = jnp.sum((gathered.reshape(B, S, k, D)
                 * w.astype(x.dtype)[..., None]), axis=2)
    return y, aux


def moe_dense(x, p, cfg):
    """Dense one-hot dispatch baseline: computes every expert for every
    token via masked combine — simple, robust, E× the FLOPs."""
    B, S, D = x.shape
    E, k = cfg.n_experts, cfg.top_k
    w, ids, aux = _router(x, p["router"], k)
    onehot = jax.nn.one_hot(ids, E, dtype=jnp.float32)          # (B,S,k,E)
    cw = jnp.sum(onehot * w[..., None], axis=2)                 # (B,S,E)
    h = jnp.einsum("bsd,edf->bsef", x, p["up"])
    g = jnp.einsum("bsd,edf->bsef", x, p["gate"])
    h = jax.nn.silu(g) * h
    y = jnp.einsum("bsef,efd->bsed", h, p["down"])
    y = jnp.sum(y * cw[..., None].astype(x.dtype), axis=2)
    return y, aux


def _ep_dispatch_body(cfg, model_axis: str, ep: int,
                      capacity_factor: float, slot_factor: float):
    """The per-PE EP dispatch body, shared by ``moe_ep_shardmap`` (real
    2-D device mesh) and ``moe_ep_sim`` (emulated (d, ep) mesh).

    Every collective inside names ``model_axis`` only, so the dispatch
    sorts/exchanges within the ep-sized expert-parallel subgroup of
    whatever mesh surrounds it — the data axis never communicates.
    """
    from repro.core import comm
    from repro.core.hypercube import _alltoall_route
    from repro.core.types import SortShard

    E, k = cfg.n_experts, cfg.top_k
    e_per = E // ep
    assert e_per >= 1

    def body(x_blk, router, up, gate, down):
        me = comm.axis_index(model_axis)
        B, S_loc, D = x_blk.shape
        T = B * S_loc
        xt = x_blk.reshape(T, D)
        w, ids, aux = _router(xt, router, k)                    # (T,k)
        N = T * k
        eids = ids.reshape(N)
        feat = jnp.repeat(xt, k, axis=0)                        # (N,D)
        src = jnp.arange(N, dtype=jnp.uint32) // np.uint32(k)
        wgt = w.reshape(N).astype(jnp.float32)

        shard = SortShard(
            keys=eids.astype(jnp.uint32),
            vals={"feat": feat, "src": src, "w": wgt,
                  "org": jnp.full((N,), me.astype(jnp.uint32))},
            count=jnp.int32(N))
        dest = eids // e_per                                    # exact splitters
        slot_cap = int(slot_factor * N / ep) + 8
        recv, drop1 = _alltoall_route(shard, dest.astype(jnp.int32),
                                      model_axis, ep, slot_cap)
        # group received items by local expert (the SSSS partition step)
        M = recv.capacity
        leid = (recv.keys.astype(jnp.int32) - me.astype(jnp.int32) * e_per)
        leid = jnp.where(recv.valid_mask(), jnp.clip(leid, 0, e_per - 1), e_per)
        cap_e = int(capacity_factor * k * T / E) + 8
        slot, kept = _group_by_expert(leid, e_per, cap_e)
        kept &= recv.valid_mask()
        flat = jnp.where(kept, leid * cap_e + slot, e_per * cap_e)
        buf = jnp.zeros((e_per * cap_e + 1, D), x_blk.dtype)
        buf = buf.at[flat].set(jnp.where(kept[:, None], recv.vals["feat"], 0))
        buf = buf[:-1].reshape(e_per, cap_e, D)
        out = _expert_ffn(buf, up, gate, down)                  # (e_per,cap,D)
        out = out.reshape(e_per * cap_e, D)
        yitem = jnp.where(kept[:, None],
                          out[jnp.clip(flat, 0, e_per * cap_e - 1)], 0)
        # route items back to their origin rank
        back = SortShard(keys=recv.keys,
                         vals={"feat": yitem, "src": recv.vals["src"],
                               "w": recv.vals["w"]},
                         count=recv.count)
        back_dest = jnp.where(recv.valid_mask(),
                              recv.vals["org"].astype(jnp.int32), ep)
        ret, drop2 = _alltoall_route(back, back_dest, model_axis, ep, slot_cap)
        y = jnp.zeros((T + 1, D), jnp.float32)
        rsrc = jnp.where(ret.valid_mask(), ret.vals["src"].astype(jnp.int32), T)
        y = y.at[rsrc].add(ret.vals["feat"].astype(jnp.float32)
                           * ret.vals["w"][:, None])
        y = y[:-1].astype(x_blk.dtype).reshape(B, S_loc, D)
        return y, aux[None], (drop1 + drop2)[None]

    return body


def moe_ep_shardmap(x, p, cfg, mesh, *, data_axes, model_axis="model",
                    capacity_factor: float = 2.0, slot_factor: float = 2.0):
    """EP layout: distributed sort-based dispatch over ``model_axis``.

    x: (B, S, D) with batch sharded over data_axes; inside the shard_map the
    sequence is additionally split over the model axis, items are exchanged
    by expert ownership with the paper's slotted all-to-all, computed, and
    routed back (vals carry the bf16 feature vectors as 2-D payload).
    ``mesh`` may carry any number of data axes — the dispatch collectives
    are relative to ``model_axis``, so each (data...)-slice's ep-subgroup
    sorts independently.
    """
    from jax.sharding import PartitionSpec as P

    ep = mesh.shape[model_axis]
    body = _ep_dispatch_body(cfg, model_axis, ep, capacity_factor,
                             slot_factor)

    dp = P(data_axes, model_axis, None)
    y, aux, drops = jax.shard_map(
        body, mesh=mesh,
        in_specs=(dp, P(), P(model_axis, None, None),
                  P(model_axis, None, None), P(model_axis, None, None)),
        out_specs=(dp, P(model_axis), P(model_axis)),
        check_vma=False,
    )(x, p["router"], p["up"], p["gate"], p["down"])
    return y, jnp.mean(aux)


def moe_ep_sim(x, p, cfg, *, d: int = 1, ep: Optional[int] = None,
               model_axis: str = "expert",
               capacity_factor: float = 2.0, slot_factor: float = 2.0):
    """EP dispatch on the **sim backend** over an emulated (d, ep) mesh.

    Runs the exact ``moe_ep_shardmap`` body with
    ``comm.sim_map(..., mesh=(d, ep))``: the batch splits into d data-axis
    rows, the sequence into ep expert-parallel blocks, and each row's
    dispatch sorts within its own ep-sized subgroup — the multi-tenant
    layout (many independent MoE replicas per host) without needing
    d·ep physical devices.  Returns (y, aux) like the shard_map path.
    """
    from repro.core import comm

    B, S, D = x.shape
    E = cfg.n_experts
    ep = ep or E
    if B % d or S % ep or E % ep:
        raise ValueError(f"B={B} S={S} E={E} not divisible by (d={d}, "
                         f"ep={ep})")
    e_per = E // ep
    body = _ep_dispatch_body(cfg, model_axis, ep, capacity_factor,
                             slot_factor)
    # (B, S, D) → (d, ep, B/d, S/ep, D): batch over data rows, sequence
    # over expert-parallel blocks — the sim image of the shard_map specs
    # P(data_axes, model_axis, None).
    xb = x.reshape(d, B // d, ep, S // ep, D)
    xb = jnp.moveaxis(xb, 2, 1)

    def tile(w, split_experts):
        if split_experts:                  # (E, ...) → per-PE (e_per, ...)
            w = w.reshape((ep, e_per) + w.shape[1:])
        else:                              # replicated across the mesh
            w = jnp.broadcast_to(w[None], (ep,) + w.shape)
        return jnp.broadcast_to(w[None], (d,) + w.shape)

    run = comm.sim_map(body, model_axis, ep, mesh=(d, ep), data_axis="data")
    y, aux, drops = run(xb, tile(p["router"], False), tile(p["up"], True),
                        tile(p["gate"], True), tile(p["down"], True))
    y = jnp.moveaxis(y, 1, 2).reshape(B, S, D)   # (d, ep, b, s, D) → (B, S, D)
    return y, jnp.mean(aux)


def moe_tp_shardmap(x, p, cfg, mesh, *, data_axes,
                    capacity_factor: float = 2.0):
    """TP layout, §Perf-optimized: group locally, run the F-sharded experts
    inside shard_map and psum the *combined tokens* (B,S,D) instead of
    letting GSPMD all-reduce the (B,E,cap,D) capacity buffer — ~cf·E/k ×
    less collective volume (the mixtral hillclimb, EXPERIMENTS.md §Perf).
    """
    from jax.sharding import PartitionSpec as P
    from repro.core import comm

    E, k = cfg.n_experts, cfg.top_k
    dp = P(data_axes, None, None)

    def body(x_blk, router, up, gate, down):
        y, aux = moe_local(x_blk, {"router": router, "up": up, "gate": gate,
                                   "down": down}, cfg,
                           capacity_factor=capacity_factor)
        y = comm.psum(y, "model")
        return y, aux[None]

    y, aux = jax.shard_map(
        body, mesh=mesh,
        in_specs=(dp, P(), P(None, None, "model"), P(None, None, "model"),
                  P(None, "model", None)),
        out_specs=(dp, P("model")),
        check_vma=False,
    )(x, p["router"], p["up"], p["gate"], p["down"])
    return y, jnp.mean(aux)


def moe_apply(x, p, cfg, mesh=None, *, data_axes=("data",),
              impl: Optional[str] = None):
    impl = impl or cfg.moe_impl
    if impl == "dense":
        return moe_dense(x, p, cfg)
    if (impl == "sort" and mesh is not None and "model" in mesh.shape
            and cfg.n_experts % mesh.shape["model"] == 0
            and x.shape[1] % mesh.shape["model"] == 0):   # decode: S=1 →
        return moe_ep_shardmap(x, p, cfg, mesh, data_axes=data_axes)
    if (impl == "sort" and getattr(cfg, "moe_tp_fused", False)
            and mesh is not None and "model" in mesh.shape):
        return moe_tp_shardmap(x, p, cfg, mesh, data_axes=data_axes)
    return moe_local(x, p, cfg)                           # local grouping

"""Mixture-of-Experts FFN with expert parallelism.

Routing (token top-k over a softmax router, renormalized; load-balance +
router-z aux losses) runs in plain GSPMD-land — token-parallel math.  The
expert computation runs inside a ``shard_map`` island over the 'model' axis:

  * experts are sharded over 'model' (E_loc = E / tp per shard) and their
    weight matrices are additionally FSDP-sharded over the batch axes; the
    island all-gathers the FSDP shards (AD turns that into the ZeRO-style
    reduce-scatter on the backward pass);
  * each shard sort-dispatches ITS OWN data-shard tokens to ITS local
    experts into fixed ``(E_loc, C, D)`` capacity buffers (pure static-shape
    argsort/searchsorted/gather — no dynamic shapes, no host sync);
  * expert FFN is one batched einsum over local experts;
  * contributions are scatter-added back to token space and ``psum`` over
    'model' combines expert + shared-expert partial outputs.

Without a mesh (unit tests, CPU examples) the identical math runs with
E_loc = E and no collectives.
"""
from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.distributed import context as dctx
from repro.models.layers import dense_init

Array = jax.Array
PyTree = Any


def init_moe(key, cfg: ModelConfig, dtype) -> PyTree:
    m = cfg.moe
    d = cfg.d_model
    ks = jax.random.split(key, 7)
    p = {
        "router": dense_init(ks[0], (d, m.num_experts), jnp.float32, scale=d**-0.5),
        "w_in": dense_init(ks[1], (m.num_experts, d, m.d_ff_expert), dtype),
        "w_gate": dense_init(ks[2], (m.num_experts, d, m.d_ff_expert), dtype),
        "w_out": dense_init(ks[3], (m.num_experts, m.d_ff_expert, d), dtype),
    }
    if m.num_shared:
        fs = m.num_shared * m.d_ff_expert
        p["shared"] = {
            "w_in": dense_init(ks[4], (d, fs), dtype),
            "w_gate": dense_init(ks[5], (d, fs), dtype),
            "w_out": dense_init(ks[6], (fs, d), dtype),
        }
    return p


def _route(x2d: Array, router: Array, top_k: int):
    """Token top-k routing. Returns (top_e, top_p, aux_losses)."""
    logits = x2d.astype(jnp.float32) @ router.astype(jnp.float32)  # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_e = jax.lax.top_k(probs, top_k)
    top_p = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)
    e = router.shape[1]
    # load-balance (Switch): E * sum_e f_e * p_e
    f_e = jnp.zeros((e,), jnp.float32).at[top_e.reshape(-1)].add(1.0)
    f_e = f_e / jnp.maximum(f_e.sum(), 1.0)
    p_e = probs.mean(axis=0)
    aux = e * jnp.sum(f_e * p_e)
    z = jnp.mean(jax.scipy.special.logsumexp(logits, axis=-1) ** 2)
    return top_e, top_p, {"router_aux": aux, "router_z": z}


def _dispatch_compute(
    x2d: Array,
    top_e: Array,
    top_p: Array,
    w_in: Array,
    w_gate: Array,
    w_out: Array,
    *,
    e_start: Array | int,
    e_loc: int,
    capacity: int,
) -> Array:
    """Capacity-buffer expert FFN for experts [e_start, e_start + e_loc)."""
    t, k = top_e.shape
    dt = x2d.dtype
    flat_e = top_e.reshape(-1)  # (T*k,)
    local_id = flat_e - e_start
    is_local = (local_id >= 0) & (local_id < e_loc)
    sort_key = jnp.where(is_local, local_id, e_loc)  # non-local -> tail bucket
    sort_idx = jnp.argsort(sort_key, stable=True)
    sorted_key = sort_key[sort_idx]
    seg_start = jnp.searchsorted(sorted_key, jnp.arange(e_loc), side="left")
    seg_end = jnp.searchsorted(sorted_key, jnp.arange(e_loc), side="right")
    slot_pos = seg_start[:, None] + jnp.arange(capacity)[None, :]  # (E_loc, C)
    valid = slot_pos < seg_end[:, None]  # capacity-drop beyond C
    slot_flat = jnp.take(sort_idx, jnp.clip(slot_pos, 0, t * k - 1))  # (E_loc, C)
    tok = slot_flat // k
    xb = jnp.take(x2d, tok, axis=0) * valid[..., None].astype(dt)  # (E_loc, C, D)
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xb, w_gate.astype(dt))) * jnp.einsum(
        "ecd,edf->ecf", xb, w_in.astype(dt)
    )
    y = jnp.einsum("ecf,efd->ecd", h, w_out.astype(dt))  # (E_loc, C, D)
    gate = jnp.take(top_p.reshape(-1), slot_flat) * valid  # (E_loc, C)
    contrib = y * gate[..., None].astype(dt)
    out = jnp.zeros_like(x2d).at[tok.reshape(-1)].add(
        contrib.reshape(-1, x2d.shape[-1])
    )
    return out


def _shared_ffn(x2d: Array, shared: PyTree) -> Array:
    dt = x2d.dtype
    h = jax.nn.silu(x2d @ shared["w_gate"].astype(dt)) * (x2d @ shared["w_in"].astype(dt))
    return h @ shared["w_out"].astype(dt)


def moe_ffn(p: PyTree, x: Array, cfg: ModelConfig) -> tuple[Array, dict[str, Array]]:
    """MoE FFN over x (B, S, D). Returns (out, aux_losses)."""
    m = cfg.moe
    b, s, d = x.shape
    x2d = x.reshape(b * s, d)
    top_e, top_p, aux = _route(x2d, p["router"], m.top_k)

    mesh = dctx.current_mesh()
    tp = dctx.model_axis_size(mesh)
    e_loc = m.num_experts // tp
    if m.num_experts % tp:
        raise ValueError(f"{m.num_experts} experts not divisible by tp={tp}")

    if mesh is None or tp == 1:
        t_tokens = x2d.shape[0]
        capacity = _capacity(t_tokens, m.top_k, m.num_experts, m.capacity_factor)
        out = _dispatch_compute(
            x2d, top_e, top_p, p["w_in"], p["w_gate"], p["w_out"],
            e_start=0, e_loc=m.num_experts, capacity=capacity,
        )
        if m.num_shared:
            out = out + _shared_ffn(x2d, p["shared"])
        return out.reshape(b, s, d), aux

    batch_axes = dctx.batch_axes(mesh)
    # Weight-sharding axes follow the ACTIVE fsdp rule (sharding.py), not the
    # mesh: at serving time fsdp=() replicates weights over the batch axes
    # and the island must not re-shard + re-gather them (measured 56 GB/step
    # of spurious all-gathers on deepseek-v2 decode_32k otherwise).
    from repro.distributed.sharding import LOGICAL_AXES

    fsdp_axes = tuple(a for a in LOGICAL_AXES.get("fsdp", ()) if a in mesh.axis_names)
    n_batch_shards = 1
    for a in batch_axes:
        n_batch_shards *= mesh.shape[a]
    # Decode / small-batch: moving 2x the expert weights over the wire to
    # meet a handful of tokens is backwards.  The weight-stationary island
    # contracts over the LOCAL D-slice and psums the (tiny) activations —
    # wire bytes O(T * F_e) instead of O(E_loc * D * F_e) per layer
    # (measured: 56 GB -> ~MBs per decode step on deepseek-v2 decode_32k).
    # Tokens are REPLICATED over the batch axes in this mode (every shard
    # computes all T tokens for its D-slice; psums complete contractions).
    weight_stationary = bool(fsdp_axes) and (b * s) * m.top_k <= 4096

    # B=1 decode and other indivisible token counts: replicate tokens over
    # the batch axes (expert parallelism still splits the work over 'model').
    token_sharded = (batch_axes and (b * s) % n_batch_shards == 0
                     and not weight_stationary)
    t_local = (b * s) // n_batch_shards if token_sharded else b * s
    capacity = _capacity(t_local, m.top_k, m.num_experts, m.capacity_factor)

    def _fsdp_index():
        idx = 0
        for a in fsdp_axes:
            idx = idx * mesh.shape[a] + jax.lax.axis_index(a)
        return idx

    def island(x_l, te_l, tp_l, w_in, w_gate, w_out, shared):
        e_start = jax.lax.axis_index(dctx.MODEL_AXIS) * e_loc
        if not weight_stationary:
            # train path: FSDP-gather the D shards (ZeRO-3 style; AD emits
            # the matching reduce-scatter on the backward pass).
            if fsdp_axes:
                w_in = jax.lax.all_gather(w_in, fsdp_axes, axis=1, tiled=True)
                w_gate = jax.lax.all_gather(w_gate, fsdp_axes, axis=1, tiled=True)
                w_out = jax.lax.all_gather(w_out, fsdp_axes, axis=2, tiled=True)
            out = _dispatch_compute(
                x_l, te_l, tp_l, w_in, w_gate, w_out,
                e_start=e_start, e_loc=e_loc, capacity=capacity,
            )
            if shared is not None:
                if fsdp_axes:
                    sh = {
                        "w_in": jax.lax.all_gather(shared["w_in"], fsdp_axes, axis=0, tiled=True),
                        "w_gate": jax.lax.all_gather(shared["w_gate"], fsdp_axes, axis=0, tiled=True),
                        "w_out": jax.lax.all_gather(shared["w_out"], fsdp_axes, axis=1, tiled=True),
                    }
                else:
                    sh = shared
                out = out + _shared_ffn(x_l, sh)
            return jax.lax.psum(out, dctx.MODEL_AXIS)

        # ---- weight-stationary decode path ---------------------------------
        t, k = te_l.shape
        dt = x_l.dtype
        d_loc = w_in.shape[1]
        x_slice = jax.lax.dynamic_slice_in_dim(x_l, _fsdp_index() * d_loc, d_loc, axis=1)
        # same static-shape dispatch as _dispatch_compute, D-sliced
        flat_e = te_l.reshape(-1)
        local_id = flat_e - e_start
        is_local = (local_id >= 0) & (local_id < e_loc)
        sort_key = jnp.where(is_local, local_id, e_loc)
        sort_idx = jnp.argsort(sort_key, stable=True)
        sorted_key = sort_key[sort_idx]
        seg_start = jnp.searchsorted(sorted_key, jnp.arange(e_loc), side="left")
        seg_end = jnp.searchsorted(sorted_key, jnp.arange(e_loc), side="right")
        slot_pos = seg_start[:, None] + jnp.arange(capacity)[None, :]
        valid = slot_pos < seg_end[:, None]
        slot_flat = jnp.take(sort_idx, jnp.clip(slot_pos, 0, t * k - 1))
        tok = slot_flat // k
        xb = jnp.take(x_slice, tok, axis=0) * valid[..., None].astype(dt)  # (E_loc,C,D_loc)
        # contract local D slice, psum to complete before the nonlinearity
        h_gate = jax.lax.psum(jnp.einsum("ecd,edf->ecf", xb, w_gate.astype(dt)), fsdp_axes)
        h_in = jax.lax.psum(jnp.einsum("ecd,edf->ecf", xb, w_in.astype(dt)), fsdp_axes)
        h = jax.nn.silu(h_gate) * h_in  # (E_loc, C, F_e)
        y_slice = jnp.einsum("ecf,efd->ecd", h, w_out.astype(dt))  # (E_loc,C,D_loc)
        gate = jnp.take(tp_l.reshape(-1), slot_flat) * valid
        contrib = y_slice * gate[..., None].astype(dt)
        out_slice = jnp.zeros_like(x_slice).at[tok.reshape(-1)].add(
            contrib.reshape(-1, d_loc))
        if shared is not None:
            hs_g = jax.lax.psum(x_slice @ shared["w_gate"].astype(dt), fsdp_axes)
            hs_i = jax.lax.psum(x_slice @ shared["w_in"].astype(dt), fsdp_axes)
            hs = jax.nn.silu(hs_g) * hs_i  # (T, Fs_loc)
            out_slice = out_slice + hs @ shared["w_out"].astype(dt)
        out = jax.lax.all_gather(out_slice, fsdp_axes, axis=1, tiled=True)
        return jax.lax.psum(out, dctx.MODEL_AXIS)

    # ---- all-to-all EP dispatch (training/prefill; cfg.moe_a2a) -----------
    # Tokens are sharded over batch AND model axes (T_cell per device);
    # assignments travel to the expert's shard via all_to_all instead of
    # replicating compute + psumming full (T_loc, D) activations — wire
    # bytes drop from O(T_loc * D) to O(T_cell * k * D) per layer.
    cell_axes = tuple(batch_axes) + (dctx.MODEL_AXIS,)
    n_cells = n_batch_shards * tp
    use_a2a = (
        cfg.moe_a2a and not weight_stationary and batch_axes
        and (b * s) % n_cells == 0
    )
    if use_a2a:
        t_cell = (b * s) // n_cells
        cap_send = _capacity(t_cell, m.top_k, m.num_experts, m.capacity_factor)

        def island_a2a(x_l, te_l, tp_l, w_in, w_gate, w_out, shared):
            if fsdp_axes:
                w_in = jax.lax.all_gather(w_in, fsdp_axes, axis=1, tiled=True)
                w_gate = jax.lax.all_gather(w_gate, fsdp_axes, axis=1, tiled=True)
                w_out = jax.lax.all_gather(w_out, fsdp_axes, axis=2, tiled=True)
            t, k = te_l.shape
            dt = x_l.dtype
            e = m.num_experts
            # slot tokens by GLOBAL expert id -> (E, C_send) send buffer
            flat_e = te_l.reshape(-1)
            sort_idx = jnp.argsort(flat_e, stable=True)
            sorted_e = flat_e[sort_idx]
            seg_start = jnp.searchsorted(sorted_e, jnp.arange(e), side="left")
            seg_end = jnp.searchsorted(sorted_e, jnp.arange(e), side="right")
            slot_pos = seg_start[:, None] + jnp.arange(cap_send)[None, :]
            valid = slot_pos < seg_end[:, None]  # (E, C_send)
            slot_flat = jnp.take(sort_idx, jnp.clip(slot_pos, 0, t * k - 1))
            tok = slot_flat // k
            xb = jnp.take(x_l, tok, axis=0) * valid[..., None].astype(dt)
            # (E, C, D) -> (tp, E_loc, C, D) -> a2a over 'model'
            xb = xb.reshape(tp, e_loc, cap_send, -1)
            xr = jax.lax.all_to_all(
                xb, dctx.MODEL_AXIS, split_axis=0, concat_axis=0, tiled=False)
            # received: (tp sources, E_loc, C, D) -> (E_loc, tp*C, D)
            xr = xr.transpose(1, 0, 2, 3).reshape(e_loc, tp * cap_send, -1)
            h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xr, w_gate.astype(dt))) \
                * jnp.einsum("ecd,edf->ecf", xr, w_in.astype(dt))
            y = jnp.einsum("ecf,efd->ecd", h, w_out.astype(dt))
            # route results back: (E_loc, tp, C, D) -> a2a -> (E, C, D)
            y = y.reshape(e_loc, tp, cap_send, -1).transpose(1, 0, 2, 3)
            yr = jax.lax.all_to_all(
                y, dctx.MODEL_AXIS, split_axis=0, concat_axis=0, tiled=False)
            yr = yr.reshape(e * cap_send, -1)
            gate = (jnp.take(tp_l.reshape(-1), slot_flat) * valid).reshape(-1)
            out = jnp.zeros_like(x_l).at[tok.reshape(-1)].add(
                yr * gate[:, None].astype(dt))
            if shared is not None:
                # shared experts stay row/col-parallel over 'model' with a
                # psum of the (small) T_cell slice
                sh = shared
                if fsdp_axes:
                    sh = {
                        "w_in": jax.lax.all_gather(shared["w_in"], fsdp_axes, axis=0, tiled=True),
                        "w_gate": jax.lax.all_gather(shared["w_gate"], fsdp_axes, axis=0, tiled=True),
                        "w_out": jax.lax.all_gather(shared["w_out"], fsdp_axes, axis=1, tiled=True),
                    }
                out = out + jax.lax.psum(_shared_ffn(x_l, sh), dctx.MODEL_AXIS)
            return out

        cell_spec = P(cell_axes, None)
        out = jax.shard_map(
            island_a2a,
            mesh=mesh,
            in_specs=(
                cell_spec, cell_spec, cell_spec,
                P(dctx.MODEL_AXIS, fsdp_axes if fsdp_axes else None, None),
                P(dctx.MODEL_AXIS, fsdp_axes if fsdp_axes else None, None),
                P(dctx.MODEL_AXIS, None, fsdp_axes if fsdp_axes else None),
                (
                    {"w_in": P(fsdp_axes if fsdp_axes else None, dctx.MODEL_AXIS),
                     "w_gate": P(fsdp_axes if fsdp_axes else None, dctx.MODEL_AXIS),
                     "w_out": P(dctx.MODEL_AXIS, fsdp_axes if fsdp_axes else None)}
                    if m.num_shared else None
                ),
            ),
            out_specs=cell_spec,
            check_vma=False,
        )(x2d, top_e, top_p, p["w_in"], p["w_gate"], p["w_out"], p.get("shared"))
        return out.reshape(b, s, d), aux

    x_spec = P(batch_axes if token_sharded else None, None)
    w_fsdp = fsdp_axes if fsdp_axes else None
    shared_specs = (
        {"w_in": P(w_fsdp, dctx.MODEL_AXIS),
         "w_gate": P(w_fsdp, dctx.MODEL_AXIS),
         "w_out": P(dctx.MODEL_AXIS, w_fsdp)}
        if m.num_shared
        else None
    )
    out = jax.shard_map(
        island,
        mesh=mesh,
        in_specs=(
            x_spec,
            x_spec,
            x_spec,
            P(dctx.MODEL_AXIS, w_fsdp, None),
            P(dctx.MODEL_AXIS, w_fsdp, None),
            P(dctx.MODEL_AXIS, None, w_fsdp),
            shared_specs,
        ),
        out_specs=x_spec,
        check_vma=False,
    )(x2d, top_e, top_p, p["w_in"], p["w_gate"], p["w_out"], p.get("shared"))
    return out.reshape(b, s, d), aux


def _capacity(tokens: int, top_k: int, num_experts: int, factor: float) -> int:
    cap = int(tokens * top_k / num_experts * factor) + 1
    return max(8, -(-cap // 8) * 8)  # round up to 8 lanes

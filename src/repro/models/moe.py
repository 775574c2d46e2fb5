"""Mixture-of-Experts layer with sort-based dropped dispatch and explicit
expert parallelism.

Design notes (DESIGN.md section 10):
  * The naive one-hot dispatch tensor (tokens, experts, capacity) is O(T*E*C)
    and OOMs at assigned scales; instead tokens are ranked into per-expert
    capacity slots with an argsort over expert ids (O(T*k log T*k) ints) and
    scattered directly into an (E_local, capacity, d) buffer.
  * Under a mesh, the layer runs inside shard_map: activations are sharded
    over the data axes and replicated over the model axis; each model rank
    owns E/mp experts, computes only its slice, and the partial outputs are
    psum'ed over the model axis.  Expert weights are additionally sharded
    over the data axis on the d_ff dim (FSDP) and all-gathered just-in-time.
  * Router math in f32; load-balance + router-z aux losses returned.
"""
from __future__ import annotations

import functools
import math
import os as _os
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig, MoEConfig
from repro.models.common import dense_init, glu_act
from repro.models.parallel import ParallelContext

# ---------------------------------------------------------------------------
# params
# ---------------------------------------------------------------------------


# Multi-pod FSDP (EXPERIMENTS.md section Perf, extension): shard the expert
# dim over BOTH the pod and model axes (e.g. 128 experts / 32 ranks) so that
# 400B-scale MoE optimizer state fits v5e HBM.  Opt-in because it changes
# which mesh the specs target (the dry-run sets it for multi-pod runs).
EXPERTS_OVER_POD = _os.environ.get("REPRO_MOE_EXPERTS_OVER_POD", "0") == "1"


def expert_axes():
    return ("pod", "model") if EXPERTS_OVER_POD else "model"


def init_moe(key, cfg: ModelConfig, dtype):
    m = cfg.moe
    d = cfg.d_model
    ks = jax.random.split(key, 5)
    E, F = m.num_experts, m.d_ff_expert
    params = {
        "router": dense_init(ks[0], d, E, jnp.float32),
        "wg": jax.random.truncated_normal(ks[1], -2, 2, (E, d, F), jnp.float32)
            .astype(dtype) * math.sqrt(1.0 / d),
        "wu": jax.random.truncated_normal(ks[2], -2, 2, (E, d, F), jnp.float32)
            .astype(dtype) * math.sqrt(1.0 / d),
        "wd": jax.random.truncated_normal(ks[3], -2, 2, (E, F, d), jnp.float32)
            .astype(dtype) * math.sqrt(1.0 / F),
    }
    # expert dim -> model axis (+ pod when enabled); d_ff -> data axis (FSDP)
    ff_ax = "data" if F % 16 == 0 else None
    e_ax = expert_axes()
    specs = {
        "router": P(None, None),
        "wg": P(e_ax, None, ff_ax),
        "wu": P(e_ax, None, ff_ax),
        "wd": P(e_ax, ff_ax, None),
    }
    if m.shared_expert_ff:
        from repro.models.common import init_mlp
        params["shared"], specs["shared"] = init_mlp(ks[4], d, m.shared_expert_ff, dtype)
    return params, specs


# ---------------------------------------------------------------------------
# shard-local dispatch/compute/combine
# ---------------------------------------------------------------------------


def _moe_shard(x_flat, router, wg, wu, wd, *, mcfg: MoEConfig, act: str,
               e_offset, capacity: int, model_axis: Optional[str]):
    """x_flat: (T, d) local tokens; wg/wu/wd: this rank's expert slice."""
    T, d = x_flat.shape
    E, k = mcfg.num_experts, mcfg.top_k
    E_local = wg.shape[0]

    # --- routing (f32) ----------------------------------------------------
    logits = x_flat.astype(jnp.float32) @ router                  # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate, eids = jax.lax.top_k(probs, k)                          # (T, k)
    gate = gate / jnp.sum(gate, axis=-1, keepdims=True)

    # --- capacity slot assignment (ints only) -----------------------------
    flat_e = eids.reshape(-1)                                     # (T*k,)
    order = jnp.argsort(flat_e, stable=True)
    se = flat_e[order]
    group_start = jnp.searchsorted(se, jnp.arange(E))
    pos_sorted = jnp.arange(T * k) - group_start[se]
    inv = jnp.zeros_like(order).at[order].set(jnp.arange(T * k))
    pos = pos_sorted[inv].reshape(T, k)                           # slot within expert

    local_e = eids - e_offset
    keep = (pos < capacity) & (local_e >= 0) & (local_e < E_local)
    # flattened destination row in the (E_local*capacity, d) buffer
    dst = jnp.where(keep, local_e * capacity + pos, E_local * capacity)

    # --- dispatch: k scatters of (T, d), no (T*k, d) gather ---------------
    buf = jnp.zeros((E_local * capacity, d), x_flat.dtype)
    for j in range(k):
        buf = buf.at[dst[:, j]].set(x_flat, mode="drop")
    buf = buf.reshape(E_local, capacity, d)

    # --- expert ffn --------------------------------------------------------
    g = jnp.einsum("ecd,edf->ecf", buf, wg)
    u = jnp.einsum("ecd,edf->ecf", buf, wu)
    h = glu_act(g, act) * u
    out_buf = jnp.einsum("ecf,efd->ecd", h, wd).reshape(E_local * capacity, d)

    # --- combine: k gathers weighted by gates ------------------------------
    out = jnp.zeros((T, d), x_flat.dtype)
    for j in range(k):
        vals = jnp.take(out_buf, dst[:, j], axis=0, mode="fill", fill_value=0)
        out = out + gate[:, j, None].astype(x_flat.dtype) * vals

    if model_axis is not None:
        out = jax.lax.psum(out, model_axis)

    # --- aux losses (identical on every model rank) ------------------------
    me = jnp.mean(probs, axis=0)                                  # (E,)
    frac = jnp.zeros((E,)).at[eids.reshape(-1)].add(1.0) / (T * k)
    lb_loss = E * jnp.sum(me * frac)
    z_loss = jnp.mean(jnp.square(jax.nn.logsumexp(logits, axis=-1)))
    return out, lb_loss, z_loss


def _capacity(tokens_local: int, mcfg: MoEConfig) -> int:
    cap = int(math.ceil(tokens_local * mcfg.top_k / mcfg.num_experts
                        * mcfg.capacity_factor))
    return max(cap, 1)


# ---------------------------------------------------------------------------
# decode path: broadcast tokens, never gather weights
# ---------------------------------------------------------------------------
# Perf iteration (EXPERIMENTS.md section Perf, llama4 decode_32k): the train
# path all-gathers each MoE layer's expert weights over the data axis (FSDP)
# — fine when amortized over 65k tokens/rank, catastrophic for 1-token decode
# (GBs of weight movement per step).  For decode we instead all-gather the
# *tokens* (KBs), compute on the resident (E/mp, d, ff/dp) weight shard, and
# psum the (T_global, d) partial outputs over BOTH axes (expert partitioning
# over 'model' + ff partial sums over 'data').
# Confirmed in EXPERIMENTS.md section Perf pair 1 (116-591x fewer collective
# bytes) and correctness-tested against the local oracle, so it is the
# framework default; set REPRO_MOE_DECODE_BROADCAST=0 to reproduce the
# baseline (weight all-gather) dry-runs.
DECODE_BROADCAST = _os.environ.get("REPRO_MOE_DECODE_BROADCAST", "1") == "1"


def _moe_decode_shard(x_all, router, wg, wu, wd, *, mcfg: MoEConfig, act: str,
                      e_offset, capacity: int, model_axis, data_axes):
    """x_all: (T_global, d) identical on every rank; wg/wu/wd: the rank's
    resident (E_local, d, ff_local) shard — no weight gathering."""
    out, lb, zl = _moe_shard(x_all, router, wg, wu, wd, mcfg=mcfg, act=act,
                             e_offset=e_offset, capacity=capacity,
                             model_axis=None)
    out = jax.lax.psum(out, (model_axis, *data_axes))
    return out, lb, zl


# ---------------------------------------------------------------------------
# public entry
# ---------------------------------------------------------------------------


def apply_moe(params, x, *, cfg: ModelConfig, pctx: ParallelContext, act: str):
    """x: (B, S, d) -> (out, aux dict)."""
    mcfg = cfg.moe
    B, S, d = x.shape
    if not pctx.enabled or pctx.manual:
        # manual: the caller is already inside a shard_map body (the split
        # pipeline's model-parallel stages) — no nested shard_map.  This rank
        # holds an E/mp expert slice; tokens are replicated over the model
        # axis, every rank ranks the full token set into the same capacity
        # slots (identical f32 router math), computes only its experts, and
        # _moe_shard psums the partial combine over the model axis.  With
        # mp == 1 (or no mesh) this is exactly the local path, so the
        # replicated pipeline's numerics are untouched.
        mp = pctx.mp_size if pctx.manual else 1
        assert mcfg.num_experts % mp == 0, (mcfg.num_experts, mp)
        e_off = 0
        if mp > 1:
            e_off = jax.lax.axis_index(pctx.model_axis) * \
                (mcfg.num_experts // mp)
        cap = _capacity(B * S, mcfg)
        out, lb, zl = _moe_shard(
            x.reshape(B * S, d), params["router"], params["wg"], params["wu"],
            params["wd"], mcfg=mcfg, act=act, e_offset=e_off, capacity=cap,
            model_axis=pctx.model_axis if mp > 1 else None)
        out = out.reshape(B, S, d)
    else:
        dp, mp = pctx.dp_size, pctx.mp_size
        over_pod = EXPERTS_OVER_POD and pctx.mesh is not None and \
            "pod" in pctx.mesh.axis_names
        n_pods = pctx.mesh.shape["pod"] if over_pod else 1
        ep = n_pods * mp
        assert B % dp == 0 or B < dp, (B, dp)
        assert mcfg.num_experts % ep == 0, (mcfg.num_experts, ep)
        batch_sharded = B % dp == 0 and B >= dp
        T_l = (B // dp if batch_sharded else B) * S
        decode_path = DECODE_BROADCAST and S == 1
        # experts over pod: tokens are pod-sharded but every expert rank must
        # see all candidate tokens -> gather over pod, slice back after psum
        cap = _capacity(B * S if decode_path else T_l * n_pods, mcfg)
        dpx = pctx.batch_spec_axes() if batch_sharded else None
        ff_ax = "data" if mcfg.d_ff_expert % 16 == 0 else None

        def shard_fn(xb, router, wg, wu, wd):
            rank = jax.lax.axis_index(pctx.model_axis)
            if over_pod:
                rank = jax.lax.axis_index("pod") * mp + rank
            e_off = rank * (mcfg.num_experts // ep)
            if decode_path:
                # gather the (tiny) token block instead of the weights;
                # reversed order => row blocks are data_axes[0]-major, which
                # matches the slice-back index below
                x_all = xb.reshape(-1, d)
                if batch_sharded:
                    for ax in reversed(pctx.data_axes):
                        x_all = jax.lax.all_gather(x_all, ax, axis=0,
                                                   tiled=True)
                # psum combines expert partitions (model) + ff partials; the
                # ff shard lives on 'data' only, never on 'pod' (pod ranks
                # hold identical shards, so summing over pod would double)
                psum_data = ("data",) if ff_ax is not None else ()
                out, lb, zl = _moe_decode_shard(
                    x_all, router, wg, wu, wd, mcfg=mcfg, act=act,
                    e_offset=e_off, capacity=cap,
                    model_axis=pctx.model_axis,
                    data_axes=psum_data)
                if batch_sharded:
                    # take back this rank's batch slice
                    idx = jax.lax.axis_index(pctx.data_axes[-1])
                    if len(pctx.data_axes) > 1:
                        outer = jax.lax.axis_index(pctx.data_axes[0])
                        idx = outer * pctx.mesh.shape[pctx.data_axes[-1]] + idx
                    out = jax.lax.dynamic_slice_in_dim(
                        out, idx * (B // dp), B // dp, axis=0)
                lb = jax.lax.pmean(lb, pctx.data_axes)
                zl = jax.lax.pmean(zl, pctx.data_axes)
                return out.reshape(xb.shape), lb, zl
            if ff_ax is not None:
                wg = jax.lax.all_gather(wg, ff_ax, axis=2, tiled=True)
                wu = jax.lax.all_gather(wu, ff_ax, axis=2, tiled=True)
                wd = jax.lax.all_gather(wd, ff_ax, axis=1, tiled=True)
            xf = xb.reshape(-1, d)
            if over_pod:
                xf = jax.lax.all_gather(xf, "pod", axis=0, tiled=True)
            out, lb, zl = _moe_shard(
                xf, router, wg, wu, wd, mcfg=mcfg, act=act,
                e_offset=e_off, capacity=cap,
                model_axis=("pod", pctx.model_axis) if over_pod
                else pctx.model_axis)
            if over_pod:
                pod_idx = jax.lax.axis_index("pod")
                out = jax.lax.dynamic_slice_in_dim(
                    out, pod_idx * (xf.shape[0] // n_pods),
                    xf.shape[0] // n_pods, axis=0)
            # aux losses averaged over data shards for reporting
            lb = jax.lax.pmean(lb, pctx.data_axes)
            zl = jax.lax.pmean(zl, pctx.data_axes)
            return out.reshape(xb.shape), lb, zl

        e_ax = ("pod", "model") if over_pod else "model"
        out, lb, zl = jax.shard_map(
            shard_fn, mesh=pctx.mesh,
            in_specs=(P(dpx, None, None), P(None, None),
                      P(e_ax, None, ff_ax), P(e_ax, None, ff_ax),
                      P(e_ax, ff_ax, None)),
            out_specs=(P(dpx, None, None), P(), P()),
        )(x, params["router"], params["wg"], params["wu"], params["wd"])

    aux = {"load_balance": lb * mcfg.load_balance_coef,
           "router_z": zl * mcfg.router_z_coef}
    if mcfg.shared_expert_ff:
        from repro.models.common import apply_mlp
        out = out + apply_mlp(params["shared"], x, act)
    return out, aux

"""True expert-parallel MoE dispatch via shard_map + all_to_all.

EXPERIMENTS.md §Perf cell-B iteration 1 showed that einsum-only dispatch
cannot express EP: XLA all-gathers the token axis because [T, E, C] wants the
same mesh axis on T and E. This module does what the annotations cannot:

  * experts shard over 'data' (E_loc = E/D per shard), each expert's FFN
    still splits over 'model' (f_loc = d_ff/T),
  * tokens one-hot-dispatch LOCALLY into per-destination-shard buffers,
  * one jax.lax.all_to_all moves token activations to their expert owners
    (bytes ~ T*topk*d, vs FSDP re-gathering every expert's weights),
  * expert FFN runs local-to-the-shard, psum over 'model' for the split f,
  * reverse all_to_all returns outputs; combine weights finish locally.

Differentiable (a2a transposes to a2a). Single-pod meshes ('data','model');
falls back to the dense-einsum path otherwise. Per-source-shard capacity
C = T_loc*topk*cf/E (same drop semantics as the dense path when nothing
overflows; tests use a generous capacity factor for exact comparison).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..configs.base import ModelConfig, MoEConfig
from ..sharding import rules


def _dispatch_combine(xt, logits, e: MoEConfig, C: int):
    """Shared with the dense path: one-hot capacity dispatch/combine."""
    T = xt.shape[0]
    gates = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    topv, topi = jax.lax.top_k(gates, e.top_k)
    topv = topv / jnp.maximum(topv.sum(-1, keepdims=True), 1e-9)
    onehot = jax.nn.one_hot(topi, e.num_experts, dtype=jnp.int32)
    flat = onehot.reshape(T * e.top_k, e.num_experts)
    pos = jnp.cumsum(flat, axis=0) * flat - 1
    pos = pos.reshape(T, e.top_k, e.num_experts)
    keep = (pos < C) & (pos >= 0)
    slot = jax.nn.one_hot(jnp.where(keep, pos, C), C + 1,
                          dtype=xt.dtype)[..., :C]
    dispatch = (slot * keep[..., None].astype(xt.dtype)).sum(1)
    combine = (slot * (topv[..., None] * keep.astype(jnp.float32))[..., None]
               ).sum(1).astype(jnp.float32)
    return dispatch, combine


def moe_ffn_ep(p, cfg: ModelConfig, x: jnp.ndarray) -> jnp.ndarray:
    """Drop-in for moe_ffn under a ('data','model') mesh; EP over 'data'."""
    mesh = rules._mesh()
    e = cfg.moe
    if (mesh is None or set(mesh.shape) != {"data", "model"}
            or e.num_experts % mesh.shape["data"]):
        from .moe import moe_ffn
        return moe_ffn(p, cfg, x)
    D = mesh.shape["data"]
    E_loc = e.num_experts // D

    def body(xs, router, wg, wu, wd):
        B, S, d = xs.shape
        T = B * S
        xt = xs.reshape(T, d)
        logits = jnp.einsum("td,de->te", xt, router)
        C = max(8, int(T * e.top_k * e.capacity_factor / e.num_experts)
                // 8 * 8)
        dispatch, combine = _dispatch_combine(xt, logits, e, C)
        xe = jnp.einsum("td,tec->ecd", xt, dispatch)       # [E, C, d] local
        # a2a: send each destination shard its E_loc experts' buffers.
        xe = xe.reshape(D, E_loc, C, d)
        xr = jax.lax.all_to_all(xe, "data", split_axis=0, concat_axis=0,
                                tiled=False)               # [D_src,E_loc,C,d]
        xr = xr.transpose(1, 0, 2, 3).reshape(E_loc, D * C, d)
        g = jnp.einsum("ecd,edf->ecf", xr, wg)             # f_loc on 'model'
        u = jnp.einsum("ecd,edf->ecf", xr, wu)
        y = jnp.einsum("ecf,efd->ecd", jax.nn.silu(g) * u, wd)
        y = jax.lax.psum(y, "model")                       # f was split
        # reverse a2a: outputs back to token owners.
        y = y.reshape(E_loc, D, C, d).transpose(1, 0, 2, 3)
        yb = jax.lax.all_to_all(y, "data", split_axis=0, concat_axis=0,
                                tiled=False)               # [D_dst,E_loc,C,d]
        ye = yb.reshape(e.num_experts, C, d)
        yt = jnp.einsum("ecd,tec->td", ye.astype(jnp.float32), combine)
        return yt.astype(xs.dtype).reshape(B, S, d)

    # Weight specs: router replicated; experts over 'data', f over 'model'.
    in_specs = (P("data", None, None), P(), P("data", None, "model"),
                P("data", None, "model"), P("data", "model", None))
    args = [x, p["router"], p["w_gate"], p["w_up"], p["w_down"]]
    f = jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                      out_specs=P("data", None, None), check_vma=False)
    out = f(*args)
    if e.num_shared:
        # Shared expert stays on the standard dense GeGLU path outside the
        # manual region (its weights are mlp-sharded over 'model').
        from .layers import geglu
        out = out + geglu(x, p["shared_gate"], p["shared_up"],
                          p["shared_down"], act=cfg.act)
    return out

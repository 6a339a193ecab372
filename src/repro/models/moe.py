"""Top-k MoE with sort-free gather dispatch (GShard semantics, dropless-ish).

Routing: softmax router, top-k experts per token, per-expert capacity
``C = ceil(T * k * capacity_factor / E)``; tokens beyond capacity are dropped
(weight 0) as in GShard [arXiv:2006.16668].  Dispatch avoids the O(T*E*C)
one-hot tensors: positions within each expert are computed with a cumulative
count, dispatch is a scatter-add into the (E, C, d) expert buffer and combine
is a gather back — O(T*k) index arrays only, which is what makes the 1M-token
train_4k cells feasible.

Experts shard over the "model" mesh axis (EP); the scatter/gather between the
token-sharded and expert-sharded layouts is partitioned by GSPMD into the
all-to-all exchanges of standard expert parallelism.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec

from repro.distributed.sharding import current_mesh, shard_activation
from repro.models.params import P

__all__ = ["moe_schema", "moe_apply"]


def moe_schema(cfg) -> dict:
    d, f, e = cfg.d_model, cfg.d_ff, cfg.n_experts
    return {
        "router": P((d, e), ("embed", "experts"), fan_in_axes=(0,)),
        "w_gate": P((e, d, f), ("experts", "embed", "expert_mlp"),
                    fan_in_axes=(1,)),
        "w_up": P((e, d, f), ("experts", "embed", "expert_mlp"),
                  fan_in_axes=(1,)),
        "w_down": P((e, f, d), ("experts", "expert_mlp", "embed"),
                    fan_in_axes=(1,),
                    scale=1.0 / math.sqrt(2 * max(cfg.n_layers, 1))),
    }


def _moe_ep_shard_map(p, cfg, x, top_p, top_e, mesh, dp_axes, nm, g=None):
    """Expert-parallel dispatch with manual collectives (shard_map).

    Under global-view GSPMD the token->expert scatter lowers to replicated
    (E*cap, d) buffers + all-reduce (measured: TBs/step — EXPERIMENTS.md
    Sec. Perf hillclimb 3).  Here every data shard dispatches its own tokens
    into a *local* per-expert buffer (capacity is per data shard, GShard
    group semantics), each model shard runs its E/nm experts, and the only
    cross-device traffic is ONE psum of the (T_local, d) combine output over
    the model axis — the same wire class as the TP MLP all-reduce.
    """
    E, K = cfg.n_experts, cfg.top_k
    B, S, d = x.shape
    g = nm if g is None else g
    e_loc = E // g                       # experts per subgroup
    dup = nm // g                        # ranks sharing a subgroup
    dp_spec = dp_axes if len(dp_axes) > 1 else dp_axes[0]
    w_spec = PartitionSpec("model", None, None) if g == nm \
        else PartitionSpec(None, None, None)

    def body(xb, tp, te, wg, wu, wd):
        Bl = xb.shape[0]
        Tl = Bl * S
        cap = int(math.ceil(Tl * K * cfg.capacity_factor / E))
        xt = xb.reshape(Tl, d)
        flat_e = te.reshape(Tl, K).T.reshape(K * Tl)          # k-major
        onehot = jax.nn.one_hot(flat_e, E, dtype=jnp.int32)
        pos_in_e = jnp.cumsum(onehot, axis=0) - onehot
        pos = jnp.take_along_axis(pos_in_e, flat_e[:, None], axis=1)[:, 0]
        keep = pos < cap
        dest = jnp.where(keep, flat_e * cap + pos, E * cap)
        token_of_slot = jnp.tile(jnp.arange(Tl), K)

        buf = jnp.zeros((E * cap + 1, d), xb.dtype)
        buf = buf.at[dest].add(xt[token_of_slot])             # local scatter
        weights = (tp.reshape(Tl, K).T.reshape(K * Tl) * keep).astype(xb.dtype)
        w_slot = jnp.zeros((E * cap + 1,), xb.dtype).at[dest].set(weights)
        tok_slot = jnp.full((E * cap + 1,), Tl, jnp.int32).at[dest].set(
            token_of_slot)

        j = jax.lax.axis_index("model")
        block = j % g                    # this rank's expert subgroup
        if g == nm:
            # weights arrive model-sharded: local slice IS the subgroup
            wg_b, wu_b, wd_b = wg, wu, wd
        else:
            wg_b = jax.lax.dynamic_slice_in_dim(wg, block * e_loc, e_loc, 0)
            wu_b = jax.lax.dynamic_slice_in_dim(wu, block * e_loc, e_loc, 0)
            wd_b = jax.lax.dynamic_slice_in_dim(wd, block * e_loc, e_loc, 0)
        my = jax.lax.dynamic_slice_in_dim(
            buf[:-1].reshape(E, cap, d), block * e_loc, e_loc, axis=0)
        h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", my, wg_b)) \
            * jnp.einsum("ecd,edf->ecf", my, wu_b)
        out = jnp.einsum("ecf,efd->ecd", h, wd_b)             # (e_loc,cap,d)

        w_my = jax.lax.dynamic_slice_in_dim(
            w_slot[:-1].reshape(E, cap), block * e_loc, e_loc, axis=0)
        t_my = jax.lax.dynamic_slice_in_dim(
            tok_slot[:-1].reshape(E, cap), block * e_loc, e_loc, axis=0)
        scale = jnp.asarray(1.0 / dup, xb.dtype)              # de-duplicate
        y = jnp.zeros((Tl + 1, d), xb.dtype).at[t_my.reshape(-1)].add(
            out.reshape(-1, d) * (w_my.reshape(-1, 1) * scale))
        y = jax.lax.psum(y[:-1], "model")                     # the ONE AR
        return y.reshape(Bl, S, d)

    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(PartitionSpec(dp_spec, None, None),
                  PartitionSpec(dp_spec, None, None),
                  PartitionSpec(dp_spec, None, None),
                  w_spec, w_spec, w_spec),
        out_specs=PartitionSpec(dp_spec, None, None),
        check_vma=False,
    )(x, top_p, top_e, p["w_gate"], p["w_up"], p["w_down"])


def moe_apply(p: dict, cfg, x: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
    """x: (B, S, d) -> (y, aux_loss)."""
    B, S, d = x.shape
    E, K = cfg.n_experts, cfg.top_k
    T = B * S
    cap = int(math.ceil(T * K * cfg.capacity_factor / E))

    xt = x.reshape(T, d)
    logits = (xt @ p["router"]).astype(jnp.float32)          # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_e = jax.lax.top_k(probs, K)                   # (T, K)
    top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)   # renormalize

    # load-balancing aux loss (Switch/GShard)
    density = jnp.mean(jax.nn.one_hot(top_e[:, 0], E), axis=0)
    density_proxy = jnp.mean(probs, axis=0)
    aux = jnp.sum(density * density_proxy) * E

    # ---- expert-parallel shard_map path (Sec. Perf hillclimb 3 fix) --------
    mesh = current_mesh()
    if mesh is not None:
        sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
        nm = sizes.get("model", 1)
        dp_axes = tuple(a for a in mesh.axis_names if a != "model")
        ndp = int(np.prod([sizes[a] for a in dp_axes])) or 1
        # gcd subgroups: when E doesn't divide the model axis (granite:
        # 40 over 16), shard experts over g = gcd(E, nm) subgroups; each
        # expert block runs on nm/g ranks and its combine contribution is
        # rescaled by g/nm so the psum stays exact.
        g = math.gcd(E, nm)
        if nm > 1 and g > 1 and B % ndp == 0:
            y = _moe_ep_shard_map(p, cfg, x,
                                  top_p.reshape(B, S, K),
                                  top_e.reshape(B, S, K), mesh, dp_axes,
                                  nm, g)
            return y, aux

    # ---- capacity positions: rank of each (token, slot) within its expert --
    flat_e = top_e.reshape(T * K)                            # slot-major? no:
    # order slots k-major so earlier k (higher gate) wins capacity first
    flat_e = top_e.T.reshape(K * T)                          # (K*T,)
    onehot = jax.nn.one_hot(flat_e, E, dtype=jnp.int32)      # (K*T, E)
    pos_in_expert = jnp.cumsum(onehot, axis=0) - onehot      # rank before me
    pos = jnp.take_along_axis(pos_in_expert, flat_e[:, None], axis=1)[:, 0]
    keep = pos < cap
    dest = jnp.where(keep, flat_e * cap + pos, E * cap)      # E*cap = dropped

    # ---- dispatch: scatter tokens into the (E*cap, d) expert buffer --------
    # Perf note (EXPERIMENTS.md Sec. Perf hillclimb 3): under global-view
    # GSPMD, both this scatter-add and the gather-based alternative
    # (index-scatter + row-gather; measured) materialize replicated buffers
    # and all-reduce them — the structural fix is a shard_map dispatch with
    # explicit all-to-alls, recorded as the identified next step.
    token_of_slot = jnp.tile(jnp.arange(T), K)               # (K*T,)
    buf = jnp.zeros((E * cap + 1, d), x.dtype)
    buf = buf.at[dest].add(xt[token_of_slot])                # dup slots: rare
    buf = buf[:-1].reshape(E, cap, d)
    buf = shard_activation(buf, ("act_experts", "capacity", "act_embed"))

    # ---- expert FFN (grouped SwiGLU over the expert axis) ------------------
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, p["w_gate"])) \
        * jnp.einsum("ecd,edf->ecf", buf, p["w_up"])
    out_buf = jnp.einsum("ecf,efd->ecd", h, p["w_down"])
    out_buf = shard_activation(out_buf, ("act_experts", "capacity", "act_embed"))

    # ---- combine: gather each slot's expert output, weight, sum over k -----
    flat_out = out_buf.reshape(E * cap, d)
    flat_out = jnp.concatenate([flat_out, jnp.zeros((1, d), x.dtype)], axis=0)
    slot_out = flat_out[dest]                                # (K*T, d)
    weights = (top_p.T.reshape(K * T) * keep).astype(x.dtype)
    y = jnp.zeros((T, d), x.dtype).at[token_of_slot].add(
        slot_out * weights[:, None])
    y = y.reshape(B, S, d)
    return shard_activation(y, ("batch", "seq", "act_embed")), aux

"""Fine-grained MoE sublayer (DeepSeekMoE: shared + routed top-k experts).

Dispatch is *sort-based with fixed expert capacity*: tokens are routed to
``top_k`` experts; per-expert buffers have static capacity
``ceil(T*K/E * capacity_factor)`` and tokens beyond capacity are dropped —
deliberately the same bounded-queue overflow semantics the Muppet engine
uses for event routing (DESIGN.md section 2).  Experts are sharded over the
``tp`` ("model") mesh axis (expert parallelism); the token->expert shuffle
lowers to all-to-all style collectives under GSPMD.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models import init_utils as iu
from repro.models.config import ModelConfig
from repro.models.context import Ctx
from repro.models.layers import ffn


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


def init(key, cfg: ModelConfig):
    m = cfg.moe
    D = cfg.d_model
    ks = jax.random.split(key, 5)
    params, specs = iu.split_tree({
        "router": iu.dense(ks[0], (D, m.n_routed_experts), (None, None),
                           scale=0.02),
        "w_gate": iu.dense(ks[1], (m.n_routed_experts, D, m.d_expert),
                           ("tp", "fsdp", None)),
        "w_in": iu.dense(ks[2], (m.n_routed_experts, D, m.d_expert),
                         ("tp", "fsdp", None)),
        "w_out": iu.dense(ks[3], (m.n_routed_experts, m.d_expert, D),
                          ("tp", None, "fsdp"), scale=1.0 / m.d_expert ** 0.5),
    })
    if m.n_shared_experts:
        sp, ss = ffn.init(ks[4], D, m.n_shared_experts * m.d_expert)
        params["shared"], specs["shared"] = sp, ss
    return params, specs


def apply(p, x, ctx: Ctx, *, cfg: ModelConfig):
    if ctx.mesh is not None and _sharded_ok(cfg, ctx):
        return apply_sharded(p, x, ctx, cfg=cfg)
    return _apply_global(p, x, ctx, cfg=cfg)


def _apply_global(p, x, ctx: Ctx, *, cfg: ModelConfig):
    m = cfg.moe
    cd = ctx.cdtype
    B, S, D = x.shape
    T = B * S
    K, E = m.top_k, m.n_routed_experts
    xt = x.reshape(T, D)

    # ---- routing ----
    logits = xt.astype(jnp.float32) @ p["router"].astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)                     # [T,E]
    gate, expert_ids = jax.lax.top_k(probs, K)                  # [T,K]
    gate = gate / jnp.sum(gate, axis=-1, keepdims=True)         # renorm (DS)

    # load-balance aux loss (Switch-style: E * sum_e f_e * p_e)
    assign = jax.nn.one_hot(expert_ids[:, 0], E, dtype=jnp.float32)
    frac = jnp.mean(assign, axis=0)
    mean_prob = jnp.mean(probs, axis=0)
    aux = m.router_aux_coef * E * jnp.sum(frac * mean_prob)

    # ---- sort-based dispatch with fixed capacity ----
    cap = min(_round_up(max(int(T * K / E * m.capacity_factor), 1), 8), T * K)
    flat_e = expert_ids.reshape(-1)                             # [T*K]
    flat_t = jnp.repeat(jnp.arange(T), K)
    flat_w = gate.reshape(-1)
    order = jnp.argsort(flat_e)
    se, st, sw = flat_e[order], flat_t[order], flat_w[order]
    # rank within each expert run (queue position)
    pos = jnp.arange(T * K) - jnp.searchsorted(se, se, side="left")
    slot = se * cap + pos
    valid = pos < cap                                           # overflow drop
    slot_safe = jnp.where(valid, slot, E * cap)                 # OOB -> dropped

    buf = jnp.zeros((E * cap, D), cd).at[slot_safe].set(
        xt[st].astype(cd), mode="drop")
    buf = buf.reshape(E, cap, D)
    buf = ctx.constrain(buf, ("experts", None, None))

    # ---- expert FFN (gated) ----
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, p["w_gate"].astype(cd)))
    h = h * jnp.einsum("ecd,edf->ecf", buf, p["w_in"].astype(cd))
    h = ctx.constrain(h, ("experts", None, None))
    out_e = jnp.einsum("ecf,efd->ecd", h, p["w_out"].astype(cd))

    # ---- combine ----
    flat_out = out_e.reshape(E * cap, D)
    contrib = flat_out[jnp.where(valid, slot, 0)]
    contrib = contrib * (sw * valid)[:, None].astype(cd)
    y = jax.ops.segment_sum(contrib, st, num_segments=T)

    if "shared" in p:
        y = y + ffn.apply(p["shared"], xt[None], ctx, act="silu")[0]
    return y.reshape(B, S, D).astype(x.dtype), aux


# --------------------------------------------------------------------------
# explicit expert-parallel dispatch (shard_map)
#
# GSPMD auto-sharding of the global sort-based dispatch degenerates into
# replicated token gathers at pod scale (measured: the deepseek train_4k
# cell was collective-dominated at ~125 s/step, 237 GB/device peak —
# EXPERIMENTS.md section Perf).  This path keeps routing LOCAL to each
# (pod, data, seq) token shard and moves tokens to their expert owners on
# the "model" axis with one all_to_all each way — the same
# bucket-exchange the Muppet engine uses for event routing
# (core/distributed.exchange), applied to MoE tokens.
# --------------------------------------------------------------------------


def _sharded_ok(cfg: ModelConfig, ctx: Ctx) -> bool:
    m = cfg.moe
    rules = ctx.rules or {}
    tp = rules.get("experts", ())
    if tp != ("model",):
        return False
    tp_size = int(ctx.mesh.shape["model"])
    return m.n_routed_experts % tp_size == 0 and ctx.phase != "decode"


def _round_up_i(x: int, mult: int) -> int:
    return (x + mult - 1) // mult * mult


def apply_sharded(p, x, ctx: Ctx, *, cfg: ModelConfig):
    from jax.sharding import PartitionSpec as P

    m = cfg.moe
    cd = ctx.cdtype
    B, S, D = x.shape
    K, E = m.top_k, m.n_routed_experts
    mesh = ctx.mesh
    rules = ctx.rules
    fsdp = rules.get("act_batch", ())
    seq_ax = rules.get("act_seq", ())
    tp = "model"
    M = int(mesh.shape[tp])
    E_loc = E // M

    b_shard = fsdp if B % max(_ax(mesh, fsdp), 1) == 0 and fsdp else ()
    s_shard = seq_ax if seq_ax and S % _ax(mesh, seq_ax) == 0 else ()
    B_loc = B // max(_ax(mesh, b_shard), 1)
    S_loc = S // max(_ax(mesh, s_shard), 1)
    T_loc = B_loc * S_loc
    cap_send = _round_up_i(max(int(T_loc * K / M * m.capacity_factor), 8),
                           8)
    cap_exp = _round_up_i(max(int(M * cap_send // E_loc), 8), 8)

    def ent(axes):
        return None if not axes else (axes if len(axes) > 1 else axes[0])

    x_spec = P(ent(b_shard), ent(s_shard), None)

    def local_moe(xl, router, wg, wi, wo):
        # xl: [B_loc, S_loc, D]; wg/wi: [E_loc, D_loc, F]; wo: [E_loc, F, D_loc]
        wg_f = jax.lax.all_gather(wg, fsdp, axis=1, tiled=True) \
            if fsdp else wg
        wi_f = jax.lax.all_gather(wi, fsdp, axis=1, tiled=True) \
            if fsdp else wi
        wo_f = jax.lax.all_gather(wo, fsdp, axis=3 - 1, tiled=True) \
            if fsdp else wo

        xt = xl.reshape(T_loc, D)
        logits = xt.astype(jnp.float32) @ router.astype(jnp.float32)
        probs = jax.nn.softmax(logits, axis=-1)
        gate, expert_ids = jax.lax.top_k(probs, K)         # [T_loc, K]
        gate = gate / jnp.sum(gate, axis=-1, keepdims=True)

        assign = jax.nn.one_hot(expert_ids[:, 0], E, dtype=jnp.float32)
        frac = jnp.mean(assign, axis=0)
        mean_prob = jnp.mean(probs, axis=0)
        aux = m.router_aux_coef * E * jnp.sum(frac * mean_prob)
        aux = jax.lax.pmean(jax.lax.pmean(aux, tp),
                            fsdp) if fsdp else jax.lax.pmean(aux, tp)

        # ---- bucket by destination model-shard (expert owner) ----
        flat_e = expert_ids.reshape(-1)                    # [T_loc*K]
        flat_t = jnp.repeat(jnp.arange(T_loc), K)
        flat_w = gate.reshape(-1).astype(jnp.float32)
        dest = flat_e // E_loc
        order = jnp.argsort(dest, stable=True)
        sdest, se, st, sw = (dest[order], flat_e[order], flat_t[order],
                             flat_w[order])
        pos = jnp.arange(T_loc * K, dtype=jnp.int32) - jnp.searchsorted(
            sdest, sdest, side="left").astype(jnp.int32)
        ok = pos < cap_send
        slot = jnp.where(ok, sdest * cap_send + pos, M * cap_send)

        send_x = jnp.zeros((M * cap_send, D), cd).at[slot].set(
            xt[st].astype(cd), mode="drop")
        send_e = jnp.full((M * cap_send,), -1, jnp.int32).at[slot].set(
            se.astype(jnp.int32) % E_loc, mode="drop")

        def a2a(v):
            return jax.lax.all_to_all(
                v.reshape((M, cap_send) + v.shape[1:]), tp, 0, 0,
                tiled=False).reshape((M * cap_send,) + v.shape[1:])

        recv_x = a2a(send_x)                               # [M*cap, D]
        recv_e = a2a(send_e)

        # ---- local expert FFN (sort by local expert id) ----
        e_sink = jnp.where(recv_e >= 0, recv_e, E_loc)
        order2 = jnp.argsort(e_sink, stable=True)
        re, rx = e_sink[order2], recv_x[order2]
        pos2 = jnp.arange(M * cap_send, dtype=jnp.int32) - \
            jnp.searchsorted(re, re, side="left").astype(jnp.int32)
        ok2 = (re < E_loc) & (pos2 < cap_exp)
        slot2 = jnp.where(ok2, re * cap_exp + pos2, E_loc * cap_exp)
        buf = jnp.zeros((E_loc * cap_exp, D), cd).at[slot2].set(
            rx, mode="drop").reshape(E_loc, cap_exp, D)

        h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, wg_f.astype(cd)))
        h = h * jnp.einsum("ecd,edf->ecf", buf, wi_f.astype(cd))
        out_e = jnp.einsum("ecf,efd->ecd", h, wo_f.astype(cd))

        # ---- undo expert sort, a2a back, combine ----
        flat_out = out_e.reshape(E_loc * cap_exp, D)
        back = jnp.zeros((M * cap_send, D), cd).at[order2].set(
            flat_out[jnp.where(ok2, slot2, 0)] *
            ok2[:, None].astype(cd), mode="drop")
        ret = a2a(back)                                    # token order

        contrib = ret[jnp.where(ok, slot, 0)] * \
            (sw * ok).astype(cd)[:, None]
        y = jax.ops.segment_sum(contrib, st, num_segments=T_loc)
        return y.reshape(B_loc, S_loc, D).astype(xl.dtype), aux

    y, aux = jax.shard_map(
        local_moe, mesh=mesh,
        in_specs=(x_spec, P(None, None), P(tp, fsdp or None, None),
                  P(tp, fsdp or None, None), P(tp, None, fsdp or None)),
        out_specs=(x_spec, P()),
        check_vma=False,
    )(x, p["router"], p["w_gate"], p["w_in"], p["w_out"])

    if "shared" in p:
        y = y + ffn.apply(p["shared"], x, ctx, act="silu")
    return y.astype(x.dtype), aux


def _ax(mesh, axes) -> int:
    n = 1
    for a in axes:
        n *= int(mesh.shape[a])
    return n

"""Mixture-of-Experts layer: sort-based capacity dispatch + grouped GEMM.

TPU-native design (DESIGN.md §3): tokens are routed top-k, then DISPATCHED
by sorting token-expert assignments — all shapes static, jit/GSPMD-clean:

  1. router softmax -> top-k (weights, expert ids) per token
  2. flatten (T*k) assignments, argsort by expert id
  3. position-in-expert via exclusive-cumsum of expert histogram;
     tokens beyond the per-expert capacity C are DROPPED (GShard-style,
     capacity_factor bounds the buffer)
  4. scatter into an (E, C, D) buffer -> batched expert GEMM
     einsum('ecd,edf->ecf') — the expert dim shards over the mesh 'model'
     axis (expert parallelism), C shards over 'data'
  5. gather back, weight by router prob, sum over k; plus optional
     always-on shared experts (DeepSeek/Qwen-MoE style)

Load-balance auxiliary loss (Switch): E * sum_e f_e * P_e.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from .layers import dense_init


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    n_experts: int
    top_k: int
    d_ff: int                      # per-expert hidden width
    n_shared: int = 0              # always-on shared experts
    capacity_factor: float = 1.25
    act: str = "swiglu"
    router_aux_weight: float = 0.01


EXPERT_PAD = 16      # pad expert count to the model-axis extent so the
#                      expert dim always shards (qwen2-moe: 60 -> 64;
#                      dead experts are never routed — the router only
#                      emits logits for the REAL experts)


def padded_experts(e: int) -> int:
    return -(-e // EXPERT_PAD) * EXPERT_PAD


def moe_params(key, d_model: int, cfg: MoEConfig, dtype=jnp.float32) -> dict:
    ks = jax.random.split(key, 5)
    e, f = cfg.n_experts, cfg.d_ff
    e_pad = padded_experts(e)
    gated = cfg.act in ("swiglu", "geglu")
    mult = 2 if gated else 1
    p = {
        "router": dense_init(ks[0], d_model, e, jnp.float32),
        "w_in": (jax.random.normal(ks[1], (e_pad, d_model, f * mult))
                 * d_model ** -0.5).astype(dtype),
        "w_out": (jax.random.normal(ks[2], (e_pad, f, d_model))
                  * f ** -0.5).astype(dtype),
    }
    if cfg.n_shared:
        fs = cfg.n_shared * f
        p["shared_w_in"] = dense_init(ks[3], d_model, fs * mult, dtype)
        p["shared_w_out"] = dense_init(ks[4], fs, d_model, dtype,
                                       scale=fs ** -0.5)
    return p


def _expert_ffn(h, w_in, w_out, act: str):
    """h: (E, C, D); returns (E, C, D)."""
    z = jnp.einsum("ecd,edf->ecf", h, w_in)
    if act in ("swiglu", "geglu"):
        gate, up = jnp.split(z, 2, axis=-1)
        inner = jax.nn.silu(gate) if act == "swiglu" else jax.nn.gelu(gate)
        z = inner * up
    elif act == "sq_relu":
        z = jnp.square(jax.nn.relu(z))
    else:
        z = jax.nn.gelu(z)
    return jnp.einsum("ecf,efd->ecd", z, w_out)


def moe_block(p, x, cfg: MoEConfig,
              dropless: bool = False) -> tuple[jax.Array, jax.Array]:
    """x: (B, S, D) -> (out (B, S, D), aux_loss scalar).

    dropless=True sizes capacity at the worst case (t*k): exact routing
    with zero drops — the decode/serving path, where t is tiny and exact
    teacher-forcing consistency matters. Training uses the bounded
    capacity_factor buffer (GShard drops)."""
    b, s, d = x.shape
    t = b * s
    e, k = cfg.n_experts, cfg.top_k
    xf = x.reshape(t, d)

    logits = jnp.einsum("td,de->te", xf.astype(jnp.float32), p["router"])
    probs = jax.nn.softmax(logits, axis=-1)                    # (T, E)
    top_w, top_e = jax.lax.top_k(probs, k)                     # (T, k)
    top_w = top_w / jnp.maximum(top_w.sum(-1, keepdims=True), 1e-9)

    # Switch aux loss: fraction routed vs mean prob, per expert
    onehot_top1 = jax.nn.one_hot(top_e[:, 0], e, dtype=jnp.float32)
    aux = e * jnp.mean(onehot_top1.mean(0) * probs.mean(0)) \
        * cfg.router_aux_weight

    # ---- sort-based dispatch (static shapes) -------------------------
    e_pad = p["w_in"].shape[0]        # experts padded to the TP extent
    if dropless:
        cap = t * k                                           # worst case
    else:
        cap = int(max(1, -(-t * k // e) * cfg.capacity_factor))  # ceil * cf
        cap = int(-(-cap // 8) * 8)                           # pad to 8
    flat_e = top_e.reshape(-1)                                 # (T*k,)
    order = jnp.argsort(flat_e)                                # (T*k,)
    sorted_e = jnp.take(flat_e, order)
    tok = order // k                                           # source token
    counts = jnp.bincount(flat_e, length=e_pad)                # (E_pad,)
    starts = jnp.concatenate([jnp.zeros(1, counts.dtype),
                              jnp.cumsum(counts)[:-1]])
    pos = jnp.arange(t * k) - jnp.take(starts, sorted_e)       # rank in expert
    keep = pos < cap
    dst = jnp.where(keep, sorted_e * cap + pos, e_pad * cap)   # trash slot

    dtype = x.dtype
    buf = jnp.zeros((e_pad * cap + 1, d), dtype).at[dst].set(
        jnp.take(xf, tok, axis=0).astype(dtype))
    ebuf = buf[: e_pad * cap].reshape(e_pad, cap, d)
    y = _expert_ffn(ebuf, p["w_in"], p["w_out"], cfg.act)      # (E, C, D)

    slots = y.reshape(e_pad * cap, d)
    gathered = jnp.take(slots, jnp.where(keep, sorted_e * cap + pos, 0),
                        axis=0) * keep[:, None]
    w_sorted = jnp.take(top_w.reshape(-1), order)
    out = jnp.zeros((t, d), dtype).at[tok].add(
        (gathered * w_sorted[:, None]).astype(dtype))

    if cfg.n_shared:
        z = jnp.einsum("td,df->tf", xf, p["shared_w_in"])
        if cfg.act in ("swiglu", "geglu"):
            gate, up = jnp.split(z, 2, axis=-1)
            inner = jax.nn.silu(gate) if cfg.act == "swiglu" \
                else jax.nn.gelu(gate)
            z = inner * up
        else:
            z = jax.nn.gelu(z)
        out = out + jnp.einsum("tf,fd->td", z, p["shared_w_out"])

    return out.reshape(b, s, d), aux


def moe_block_sharded(p, x, cfg: MoEConfig, mesh,
                      dropless: bool = False) -> tuple[jax.Array, jax.Array]:
    """Expert-parallel MoE via shard_map (EXPERIMENTS.md §Perf kimi
    iteration 1).

    The pjit/GSPMD lowering of the sort-based dispatch emits generic
    distributed gathers between the token sharding (data) and the expert
    sharding (model) — mask-and-all-reduce over the FULL (T*k, D)
    dispatch tensor, ~0.5 TB/layer at kimi scale. This version makes the
    locality explicit:

      - each (data i, model j) device routes ITS tokens to ITS experts
        (E_loc = E/model per shard) with purely local sort/scatter;
      - expert weights are stored (E x D) sharded over (model x data)
        (8 GB/chip for the 1T model) and FSDP-all-gathered over 'data'
        just-in-time for the grouped GEMM;
      - un-dispatch is a local scatter; the (T_loc, D) partials psum
        over 'model' (tokens routed to other shards' experts are zero).

    Per-device per-layer wire: w gather (~2 GB) + out psum (~1 GB) —
    vs ~30 GB of involuntary gathers in the GSPMD path.

    Requires E_pad % model == 0 and D % data == 0 (callers fall back to
    moe_block otherwise). Expert counts are padded to the model-axis
    extent (qwen2-moe: 60 -> 64; dead experts receive no router logits,
    so they are never routed — §Perf G6)."""
    from jax.sharding import PartitionSpec as P

    axes = mesh.axis_names
    dp = tuple(a for a in axes if a != "model")
    model_n = mesh.shape["model"]
    e, k = cfg.n_experts, cfg.top_k
    e_pad = p["w_in"].shape[0]
    e_loc = e_pad // model_n
    b, s, d = x.shape

    def body(x_loc, router, w_in, w_out):
        bl, sl, _ = x_loc.shape
        t = bl * sl
        xf = x_loc.reshape(t, d)
        logits = jnp.einsum("td,de->te", xf.astype(jnp.float32), router)
        probs = jax.nn.softmax(logits, axis=-1)
        top_w, top_e = jax.lax.top_k(probs, k)
        top_w = top_w / jnp.maximum(top_w.sum(-1, keepdims=True), 1e-9)

        onehot_top1 = jax.nn.one_hot(top_e[:, 0], e, dtype=jnp.float32)
        aux = e * jnp.mean(onehot_top1.mean(0) * probs.mean(0)) \
            * cfg.router_aux_weight
        aux = jax.lax.pmean(aux, dp)          # identical across 'model'

        if dropless:
            cap = t * k
        else:
            cap = int(max(1, -(-t * k // e) * cfg.capacity_factor))
            cap = int(-(-cap // 8) * 8)

        # ---- local dispatch restricted to MY experts ------------------
        m_idx = jax.lax.axis_index("model")
        e_lo = m_idx * e_loc
        flat_e = top_e.reshape(-1)
        flat_w = jnp.take(top_w.reshape(-1), jnp.arange(t * k))
        tok = jnp.arange(t * k) // k
        mine = (flat_e >= e_lo) & (flat_e < e_lo + e_loc)
        local_e = jnp.where(mine, flat_e - e_lo, e_loc)   # e_loc = trash
        order = jnp.argsort(local_e)
        sorted_le = jnp.take(local_e, order)
        sorted_tok = jnp.take(tok, order)
        counts = jnp.bincount(local_e, length=e_loc + 1)
        starts = jnp.concatenate([jnp.zeros(1, counts.dtype),
                                  jnp.cumsum(counts)[:-1]])
        pos = jnp.arange(t * k) - jnp.take(starts, sorted_le)
        keep = (sorted_le < e_loc) & (pos < cap)
        dst = jnp.where(keep, sorted_le * cap + pos, e_loc * cap)

        dtype = x_loc.dtype
        buf = jnp.zeros((e_loc * cap + 1, d), dtype).at[dst].set(
            jnp.take(xf, sorted_tok, axis=0).astype(dtype))
        ebuf = buf[: e_loc * cap].reshape(e_loc, cap, d)

        # ---- FSDP weight gather over 'data' ---------------------------
        w_in_full = jax.lax.all_gather(w_in, "data", axis=1, tiled=True)
        w_out_full = jax.lax.all_gather(w_out, "data", axis=1, tiled=True)
        y = _expert_ffn(ebuf, w_in_full, w_out_full, cfg.act)

        # ---- local un-dispatch + model-axis reduction ------------------
        slots = y.reshape(e_loc * cap, d)
        gathered = jnp.take(slots, jnp.where(keep, dst, 0), axis=0) \
            * keep[:, None]
        wgt = jnp.take(flat_w, order)
        partial = jnp.zeros((t, d), dtype).at[sorted_tok].add(
            (gathered * wgt[:, None]).astype(dtype))
        out = jax.lax.psum(partial, "model")
        return out.reshape(bl, sl, d), aux

    dp_spec = dp if len(dp) > 1 else dp[0]
    out, aux = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(dp_spec, None, None), P(None, None),
                  P("model", "data", None), P("model", "data", None)),
        out_specs=(P(dp_spec, None, None), P()),
        check_vma=False,
    )(x, p["router"], p["w_in"], p["w_out"])

    if cfg.n_shared:
        xf = x.reshape(b * s, d)
        z = jnp.einsum("td,df->tf", xf, p["shared_w_in"])
        if cfg.act in ("swiglu", "geglu"):
            gate, up = jnp.split(z, 2, axis=-1)
            inner = jax.nn.silu(gate) if cfg.act == "swiglu" \
                else jax.nn.gelu(gate)
            z = inner * up
        else:
            z = jax.nn.gelu(z)
        out = out + jnp.einsum("tf,fd->td", z,
                               p["shared_w_out"]).reshape(b, s, d)
    return out, aux


def sharded_moe_applicable(cfg: MoEConfig, mesh, d_model: int,
                           batch: int | None = None) -> bool:
    if (mesh is None or "model" not in mesh.axis_names
            or "data" not in mesh.axis_names
            or padded_experts(cfg.n_experts) % mesh.shape["model"] != 0
            or d_model % mesh.shape["data"] != 0):
        return False
    if batch is not None:
        dp = 1
        for a in mesh.axis_names:
            if a != "model":
                dp *= mesh.shape[a]
        if batch % dp != 0:
            return False               # e.g. long_500k batch=1
    return True


def moe_block_dense_ref(p, x, cfg: MoEConfig):
    """O(E) dense oracle (every expert computes every token) — test-only
    reference for the dispatch path, no capacity drops."""
    b, s, d = x.shape
    xf = x.reshape(-1, d)
    logits = jnp.einsum("td,de->te", xf.astype(jnp.float32), p["router"])
    probs = jax.nn.softmax(logits, axis=-1)
    top_w, top_e = jax.lax.top_k(probs, cfg.top_k)
    top_w = top_w / jnp.maximum(top_w.sum(-1, keepdims=True), 1e-9)
    e_pad = p["w_in"].shape[0]
    all_out = _expert_ffn(jnp.broadcast_to(xf, (e_pad,) + xf.shape),
                          p["w_in"], p["w_out"], cfg.act)      # (E, T, D)
    gate = jnp.zeros((xf.shape[0], e_pad), jnp.float32)
    gate = gate.at[jnp.arange(xf.shape[0])[:, None], top_e].add(top_w)
    out = jnp.einsum("te,etd->td", gate, all_out.astype(jnp.float32))
    if cfg.n_shared:
        z = jnp.einsum("td,df->tf", xf, p["shared_w_in"])
        if cfg.act in ("swiglu", "geglu"):
            g, u = jnp.split(z, 2, axis=-1)
            z = (jax.nn.silu(g) if cfg.act == "swiglu" else jax.nn.gelu(g)) * u
        else:
            z = jax.nn.gelu(z)
        out = out + jnp.einsum("tf,fd->td", z, p["shared_w_out"])
    return out.reshape(b, s, d).astype(x.dtype)

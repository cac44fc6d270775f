"""Cell bundles: for every (arch x shape) cell, the concrete step function
that the dry-run lowers and the smoke tests execute.

A CellBundle packages:
  - fn(params?, opt_state?, batch, step?) — the jit-able step,
  - arg_specs: ShapeDtypeStruct trees (dry-run lowering, NO allocation),
  - shardings(mesh): PartitionSpec trees matching arg_specs,
  - init_args(rng): real (reduced) arrays for CPU smoke tests.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from ..configs import get_arch
from ..configs.base import sds
from ..models import recsys as recsys_m
from ..models import schnet as schnet_m
from ..models import transformer as tfm
from ..train.optimizer import Optimizer, adafactor, adamw
from . import sharding as shd

ADAFACTOR_THRESHOLD = 100e9        # params above this use factored state

# Gradient-accumulation (microbatch) factors for the FULL train cells:
# sized so per-chip activation temp fits a 16GB v5e (per-layer scan
# carries scale with microbatch tokens; see EXPERIMENTS.md §Perf for the
# before/after memory trail). Reduced/smoke configs always use 1.
TRAIN_ACCUM_STEPS = {
    "mistral-nemo-12b": 8,
    "nemotron-4-15b": 16,
    "qwen1.5-32b": 16,
    "kimi-k2-1t-a32b": 8,
    "qwen2-moe-a2.7b": 8,
    "bert4rec": 16,           # 65k x 200-seq Cloze batches
}


def effective_accum(preferred: int, global_batch: int, mesh) -> int:
    """Microbatches must keep the PER-MICROBATCH global batch divisible
    by (and >= ) the DP extent, or batch sharding degrades to
    replication (and the shard_map MoE falls back to GSPMD). Clamp the
    preferred factor to global_batch // dp."""
    if mesh is None:
        return preferred
    dp = 1
    for a in mesh.axis_names:
        if a != "model":
            dp *= mesh.shape[a]
    return max(1, min(preferred, global_batch // dp))


def grad_accum_value_and_grad(loss_fn, accum: int):
    """value_and_grad with lax.scan gradient accumulation over `accum`
    microbatches; grads accumulate in PARAM dtype (bf16 for the big
    archs — fp32 accumulators for a 1T-param model would blow the
    per-chip budget).

    SHARDING-CRITICAL reshape: (B, ...) -> (B/k, k, ...) -> swap, NOT
    (k, B/k, ...). The direct reshape is ambiguous to GSPMD, which then
    moves the batch sharding onto the ACCUM dim — every device ends up
    holding a FULL microbatch and data parallelism silently vanishes
    (observed: bert4rec train logits 16x oversized; EXPERIMENTS §Perf
    G7). Splitting B as (outer=B/k, inner=k) keeps the DP sharding on
    the sample dim through the reshape."""

    def split(x):
        return x.reshape((x.shape[0] // accum, accum) + x.shape[1:]) \
                .swapaxes(0, 1)

    def fn(params, batch):
        micro = jax.tree.map(split, batch)
        zero_g = jax.tree.map(lambda p: jnp.zeros(p.shape, p.dtype), params)

        def body(carry, mb):
            loss_acc, g_acc = carry
            loss, g = jax.value_and_grad(loss_fn)(params, mb)
            g_acc = jax.tree.map(
                lambda a, b: a + b.astype(a.dtype), g_acc, g)
            return (loss_acc + loss, g_acc), None

        (loss_sum, g_sum), _ = jax.lax.scan(body, (0.0, zero_g), micro)
        inv = 1.0 / accum
        return loss_sum * inv, jax.tree.map(lambda g: g * inv, g_sum)

    return fn


@dataclasses.dataclass
class CellBundle:
    arch: str
    shape: str
    kind: str
    fn: Callable
    arg_specs: tuple
    sharding_fn: Callable        # mesh -> tuple of spec trees (in_shardings)
    model_cfg: Any
    optimizer: Optional[str] = None
    donate_argnums: tuple = ()
    notes: str = ""
    # mesh-parameterized step (shard_map cells): lower() prefers this
    fn_factory: Optional[Callable] = None

    def lower(self, mesh):
        # NOTE: no re-sanitize here — the family spec functions sanitize
        # where they intend to; deliberate UNEVEN shards (e.g. the 1e6-row
        # candidate table over 256 devices) must survive (GSPMD pads).
        fn = self.fn_factory(mesh) if self.fn_factory else self.fn
        in_shardings = self.sharding_fn(mesh)
        in_shardings = jax.tree.map(
            lambda spec_tree: shd.named(mesh, spec_tree),
            in_shardings,
            is_leaf=lambda x: isinstance(x, P))
        out_shardings = self.out_shardings(in_shardings)
        with mesh:
            kw = {} if out_shardings is None else \
                {"out_shardings": out_shardings}
            jitted = jax.jit(fn, in_shardings=in_shardings,
                             donate_argnums=self.donate_argnums, **kw)
            return jitted.lower(*self.arg_specs)

    def out_shardings(self, in_shardings):
        """Steady-state output shardings: iterated steps must emit
        outputs in the SAME layout they consume (params/opt for train,
        KV cache for decode) or every step pays a reshard."""
        if self.kind == "train":
            return (in_shardings[0], in_shardings[1], None)
        if self.kind == "decode":
            b = in_shardings[1]
            return (None, b["cache_k"], b["cache_v"], None)
        return None


# ---------------------------------------------------------------------------
# LM family
# ---------------------------------------------------------------------------
def _lm_optimizer(cfg) -> tuple[str, Optimizer]:
    if cfg.n_params() > ADAFACTOR_THRESHOLD:
        return "adafactor", adafactor()
    return "adamw", adamw()


def _lm_bundle(arch_name: str, shape: str, reduced: bool) -> CellBundle:
    import dataclasses as dc

    spec = get_arch(arch_name)
    cfg = spec.model_config(reduced)
    cell = spec.cell(shape)
    batch_specs = spec.input_specs(shape, reduced)
    params_shape = tfm.params_shape(cfg)
    long_ctx = shape.startswith("long")

    def cfg_for(mesh):
        """Inject the mesh for the explicit shard_map MoE path."""
        if cfg.moe is None or mesh is None:
            return cfg
        return dc.replace(cfg, moe_mesh=mesh)

    if cell.kind == "train":
        opt_name, opt = _lm_optimizer(cfg)
        opt_shape = jax.eval_shape(opt.init, params_shape)
        pref_accum = 1 if reduced else TRAIN_ACCUM_STEPS.get(arch_name, 1)
        global_batch = batch_specs["tokens"].shape[0]

        def make_fn(mesh=None):
            c = cfg_for(mesh)
            accum = effective_accum(pref_accum, global_batch, mesh)
            vg = grad_accum_value_and_grad(
                lambda p, b: tfm.loss_fn(p, b, c), accum) if accum > 1 \
                else (lambda p, b: jax.value_and_grad(
                    lambda pp: tfm.loss_fn(pp, b, c))(p))

            def fn(params, opt_state, batch, step):
                loss, grads = vg(params, batch)
                new_p, new_o = opt.update(grads, opt_state, params, step)
                return new_p, new_o, loss

            return fn

        def shard_fn(mesh):
            pspec = shd.lm_param_specs(params_shape, mesh)
            ospec = shd.zero1_opt_specs(pspec, opt_shape, mesh)
            bspec = shd.lm_batch_specs(batch_specs, mesh, cfg, "train")
            return (pspec, ospec, bspec, P())

        return CellBundle(arch_name, shape, cell.kind, make_fn(),
                          (params_shape, opt_shape, batch_specs,
                           sds((), jnp.int32)),
                          shard_fn, cfg, opt_name,
                          donate_argnums=(0, 1),   # params/opt updated
                          fn_factory=make_fn)

    if cell.kind == "prefill":
        seq = batch_specs["tokens"].shape[1]

        def make_fn(mesh=None):
            c = cfg_for(mesh)

            def fn(params, batch):
                return tfm.prefill(params, batch["tokens"], c,
                                   cache_size=seq)

            return fn

        def shard_fn(mesh):
            pspec = shd.lm_param_specs(params_shape, mesh)
            bspec = shd.lm_batch_specs(batch_specs, mesh, cfg, "prefill")
            return (pspec, bspec)

        return CellBundle(arch_name, shape, cell.kind, make_fn(),
                          (params_shape, batch_specs), shard_fn, cfg,
                          fn_factory=make_fn)

    if cell.kind == "decode":
        def make_fn(mesh=None):
            c = cfg_for(mesh)

            def fn(params, batch):
                cache = {"k": batch["cache_k"], "v": batch["cache_v"]}
                logits, new_cache, new_len = tfm.decode_step(
                    params, batch["tokens"], cache, batch["cache_len"], c)
                return logits, new_cache["k"], new_cache["v"], new_len

            return fn

        def shard_fn(mesh):
            pspec = shd.lm_param_specs(params_shape, mesh)
            bspec = shd.lm_batch_specs(batch_specs, mesh, cfg, "decode",
                                       long_context=long_ctx)
            return (pspec, bspec)

        return CellBundle(arch_name, shape, cell.kind, make_fn(),
                          (params_shape, batch_specs), shard_fn, cfg,
                          donate_argnums=(1,),   # cache updated in place
                          fn_factory=make_fn)

    assert cell.kind == "encode"

    def fn(params, batch):
        return tfm.forward_pooled(params, batch["tokens"], cfg)

    def shard_fn(mesh):
        pspec = shd.lm_param_specs(params_shape, mesh)
        bspec = shd.lm_batch_specs(batch_specs, mesh, cfg, "encode")
        return (pspec, bspec)

    return CellBundle(arch_name, shape, cell.kind, fn,
                      (params_shape, batch_specs), shard_fn, cfg)


# ---------------------------------------------------------------------------
# GNN family (schnet)
# ---------------------------------------------------------------------------
def _gnn_bundle(arch_name: str, shape: str, reduced: bool) -> CellBundle:
    from ..configs import schnet as schnet_cfg
    spec = get_arch(arch_name)
    cfg = spec.model_config(reduced, shape)
    batch_specs = spec.input_specs(shape, reduced)
    molecular = "atom_z" in batch_specs
    params_shape = jax.eval_shape(
        lambda: schnet_m.init_params(jax.random.PRNGKey(0), cfg))
    opt = adamw()
    opt_shape = jax.eval_shape(opt.init, params_shape)
    info = (schnet_cfg.SHAPES_REDUCED if reduced
            else schnet_cfg.SHAPES)[shape]

    if molecular:
        n_graphs = info["graphs"]

        def loss(params, batch):
            return schnet_m.energy_loss(params, cfg,
                                        dict(batch, n_graphs=n_graphs))
    else:
        def loss(params, batch):
            return schnet_m.node_class_loss(params, cfg, batch)

    def fn(params, opt_state, batch, step):
        l, grads = jax.value_and_grad(loss)(params, batch)
        new_p, new_o = opt.update(grads, opt_state, params, step)
        return new_p, new_o, l

    def shard_fn(mesh):
        pspec = shd.gnn_param_specs(params_shape, mesh)
        ospec = jax.tree.map(lambda l: P(*([None] * len(l.shape))),
                             opt_shape)
        bspec = shd.gnn_batch_specs(batch_specs, mesh)
        return (pspec, ospec, bspec, P())

    return CellBundle(arch_name, shape, "train", fn,
                      (params_shape, opt_shape, batch_specs,
                       sds((), jnp.int32)),
                      shard_fn, cfg, "adamw", donate_argnums=(0, 1))


# ---------------------------------------------------------------------------
# RecSys family
# ---------------------------------------------------------------------------
_RECSYS_FNS = {
    "fm": (recsys_m.fm_init, recsys_m.fm_loss, recsys_m.fm_forward),
    "wide-deep": (recsys_m.widedeep_init, recsys_m.widedeep_loss,
                  recsys_m.widedeep_forward),
}


def _recsys_bundle(arch_name: str, shape: str, reduced: bool) -> CellBundle:
    spec = get_arch(arch_name)
    cfg = spec.model_config(reduced)
    cell = spec.cell(shape)
    batch_specs = spec.input_specs(shape, reduced)

    # --- retrieval: params-free fused top-k scoring ---------------------
    if cell.kind == "retrieval":
        k_top = min(100, batch_specs["candidates"].shape[0])

        def make_fn(mesh):
            # shard_map = the DESIGN.md distribution model, verbatim:
            # every device scores its candidate shard and emits a local
            # top-k; the global top-k is an all-gather of k candidates
            # per device (devices x k x 8 B on the wire) + a tiny merge.
            # (XLA's SPMD partitioner falls back to all-gathering the
            # FULL score vector for a global variadic sort — §Perf
            # retrieval iteration 3.)
            every = tuple(mesh.axis_names)
            n_total = batch_specs["candidates"].shape[0]
            n_dev = int(np.prod([mesh.shape[a] for a in every]))
            n_loc = n_total // n_dev
            k_loc = min(k_top, n_loc)     # tiny shards on test meshes

            def local_fn(batch):
                q = batch["query"].astype(jnp.float32)      # (B, d) repl
                c = batch["candidates"].astype(jnp.float32)  # local shard
                m = batch["candidate_mask"]
                scores = jnp.einsum("bd,nd->bn", q, c)
                scores = jnp.where(m[None, :], scores, -jnp.inf)
                s1, i1 = jax.lax.top_k(scores, k_loc)        # local top-k
                dev = jnp.int32(0)
                for ax in every:
                    dev = dev * mesh.shape[ax] + jax.lax.axis_index(ax)
                gi = i1.astype(jnp.int32) + dev * n_loc
                s_all = jax.lax.all_gather(s1, every, axis=1, tiled=True)
                i_all = jax.lax.all_gather(gi, every, axis=1, tiled=True)
                s2, pos = jax.lax.top_k(s_all,
                                        min(k_top, n_dev * k_loc))
                return s2, jnp.take_along_axis(i_all, pos, axis=1)

            # outputs ARE replicated (post-all_gather merge) but the
            # static varying-axis checker can't prove it
            return jax.shard_map(
                local_fn, mesh=mesh,
                in_specs=({"query": P(), "candidates": P(every, None),
                           "candidate_mask": P(every)},),
                out_specs=(P(), P()), check_vma=False)

        def shard_fn(mesh):
            return (shd.recsys_batch_specs(batch_specs, mesh),)

        from .mesh import make_host_mesh
        host_fn = make_fn(make_host_mesh(1, 1)) if reduced else None
        return CellBundle(arch_name, shape, cell.kind, host_fn,
                          (batch_specs,), shard_fn, cfg,
                          fn_factory=make_fn)

    # --- model init / loss / forward per arch ---------------------------
    if arch_name == "bert4rec":
        params_shape = tfm.params_shape(cfg)

        def loss_f(params, batch):
            return recsys_m.bert4rec_loss(params, cfg, batch)

        def fwd_f(params, batch):
            hidden, _ = tfm.forward(params, batch["tokens"], cfg)
            return tfm.logits_fn(params, hidden[:, -1:])[:, 0]

        param_spec_fn = functools.partial(shd.lm_param_specs, params_shape)
    elif arch_name == "dlrm-mlperf":
        params_shape = jax.eval_shape(
            lambda: recsys_m.dlrm_init(jax.random.PRNGKey(0), cfg))

        def loss_f(params, batch):
            return recsys_m.dlrm_loss(params, cfg, batch)

        def fwd_f(params, batch):
            return recsys_m.dlrm_forward(params, cfg, batch["dense"],
                                         batch["sparse_ids"])

        param_spec_fn = functools.partial(shd.recsys_param_specs,
                                          params_shape)
    else:
        init_f, loss_raw, fwd_raw = _RECSYS_FNS[arch_name]
        params_shape = jax.eval_shape(
            lambda: init_f(jax.random.PRNGKey(0), cfg))

        def loss_f(params, batch):
            return loss_raw(params, cfg, batch)

        def fwd_f(params, batch):
            return fwd_raw(params, cfg, batch["ids"])

        param_spec_fn = functools.partial(shd.recsys_param_specs,
                                          params_shape)

    if cell.kind == "train":
        opt = adamw()
        opt_shape = jax.eval_shape(opt.init, params_shape)
        accum = 1 if reduced else TRAIN_ACCUM_STEPS.get(arch_name, 1)
        vg = grad_accum_value_and_grad(loss_f, accum) if accum > 1 \
            else jax.value_and_grad(loss_f)

        def fn(params, opt_state, batch, step):
            l, grads = vg(params, batch)
            new_p, new_o = opt.update(grads, opt_state, params, step)
            return new_p, new_o, l

        def shard_fn(mesh):
            pspec = param_spec_fn(mesh)
            ospec = shd.zero1_opt_specs(pspec, opt_shape, mesh)
            bspec = shd.recsys_batch_specs(batch_specs, mesh)
            return (pspec, ospec, bspec, P())

        return CellBundle(arch_name, shape, cell.kind, fn,
                          (params_shape, opt_shape, batch_specs,
                           sds((), jnp.int32)),
                          shard_fn, cfg, "adamw", donate_argnums=(0, 1))

    assert cell.kind == "serve"

    def fn(params, batch):
        return fwd_f(params, batch)

    def shard_fn(mesh):
        return (param_spec_fn(mesh),
                shd.recsys_batch_specs(batch_specs, mesh))

    return CellBundle(arch_name, shape, cell.kind, fn,
                      (params_shape, batch_specs), shard_fn, cfg)


# ---------------------------------------------------------------------------
# public API
# ---------------------------------------------------------------------------
def build_cell(arch_name: str, shape: str,
               reduced: bool = False) -> CellBundle:
    spec = get_arch(arch_name)
    if spec.family in ("lm", "lm-encoder"):
        return _lm_bundle(arch_name, shape, reduced)
    if spec.family == "gnn":
        return _gnn_bundle(arch_name, shape, reduced)
    if spec.family == "recsys":
        return _recsys_bundle(arch_name, shape, reduced)
    raise ValueError(f"unknown family {spec.family}")


def build_probe_cell(arch_name: str, shape: str,
                     n_layers: int) -> CellBundle:
    """Roofline probe variant: full dims but only `n_layers` layers,
    PYTHON-UNROLLED (no lax.scan) and accum=1, so XLA cost_analysis sees
    every op. Two probes (L=1, L=2) + linear extrapolation recover the
    true per-step totals (benchmarks/roofline.py)."""
    import dataclasses as dc

    from ..configs import base as cfg_base

    spec = get_arch(arch_name)
    if spec.family in ("lm", "lm-encoder") or arch_name == "bert4rec":
        base_cfg = spec.model_config(False)
        probe_cfg = dc.replace(base_cfg, n_layers=n_layers,
                               unroll_layers=True)
        if spec.family == "lm":
            from ..configs.lm_family import lm_input_specs
            specs_fn = lambda s, reduced=False: lm_input_specs(  # noqa
                probe_cfg, s, reduced)
        else:
            specs_fn = spec.input_specs
        probe_spec = dc.replace(
            spec, model_config=lambda reduced=False: probe_cfg,
            input_specs=specs_fn)
    elif spec.family == "gnn":
        base_cfg = spec.model_config(False, shape)
        probe_cfg = dc.replace(base_cfg, n_interactions=n_layers,
                               unroll_layers=True)
        probe_spec = dc.replace(
            spec,
            model_config=lambda reduced=False, s=shape: probe_cfg)
    else:
        return build_cell(arch_name, shape, reduced=False)

    saved_spec = cfg_base._REGISTRY[arch_name]
    saved_accum = dict(TRAIN_ACCUM_STEPS)
    cfg_base._REGISTRY[arch_name] = probe_spec
    TRAIN_ACCUM_STEPS.clear()              # probes use accum=1
    try:
        return build_cell(arch_name, shape, reduced=False)
    finally:
        cfg_base._REGISTRY[arch_name] = saved_spec
        TRAIN_ACCUM_STEPS.update(saved_accum)


# ---------------------------------------------------------------------------
# smoke-test batch materialization (reduced configs, real arrays)
# ---------------------------------------------------------------------------
def make_smoke_args(bundle: CellBundle, seed: int = 0) -> tuple:
    """Materialize real (reduced) arrays matching bundle.arg_specs."""
    rng = np.random.default_rng(seed)
    key = jax.random.PRNGKey(seed)
    arch, cfg = bundle.arch, bundle.model_cfg
    spec_args = bundle.arg_specs

    def batch_arrays(batch_specs: dict) -> dict:
        out = {}
        for name, s in batch_specs.items():
            shape, dtype = tuple(s.shape), s.dtype
            if name in ("tokens",):
                vocab = getattr(cfg, "vocab", 100)
                out[name] = jnp.asarray(
                    rng.integers(4, vocab, shape), jnp.int32)
            elif name == "labels":
                if np.issubdtype(dtype, np.floating):
                    out[name] = jnp.asarray(
                        rng.integers(0, 2, shape).astype(np.float32))
                else:
                    hi = getattr(cfg, "vocab", None) or \
                        getattr(cfg, "n_classes", None) or 100
                    out[name] = jnp.asarray(
                        rng.integers(0, hi, shape), jnp.int32)
            elif name in ("cache_k", "cache_v"):
                out[name] = jnp.zeros(shape, dtype)
            elif name == "cache_len":
                out[name] = jnp.asarray(2, jnp.int32)
            elif name == "edge_index":
                n_nodes = _n_nodes_of(bundle)
                out[name] = jnp.asarray(
                    rng.integers(0, n_nodes, shape), jnp.int32)
            elif name == "edge_dist":
                out[name] = jnp.asarray(
                    (rng.random(shape) * 9).astype(np.float32))
            elif name == "node_feat":
                out[name] = jnp.asarray(
                    rng.standard_normal(shape).astype(np.float32))
            elif name == "atom_z":
                out[name] = jnp.asarray(rng.integers(1, 50, shape),
                                        jnp.int32)
            elif name == "graph_ids":
                n_graphs = _n_graphs_of(bundle)
                per = shape[0] // n_graphs
                out[name] = jnp.asarray(
                    np.repeat(np.arange(n_graphs), per).astype(np.int32))
            elif name == "energy":
                out[name] = jnp.asarray(
                    rng.standard_normal(shape).astype(np.float32))
            elif name == "ids":
                vocab = cfg.total_vocab
                out[name] = jnp.asarray(rng.integers(0, vocab, shape),
                                        jnp.int32)
            elif name == "dense":
                out[name] = jnp.asarray(rng.random(shape).astype(np.float32))
            elif name == "sparse_ids":
                vmax = min(cfg.table_sizes)
                out[name] = jnp.asarray(rng.integers(0, vmax, shape),
                                        jnp.int32)
            elif name in ("query", "candidates"):
                x = rng.standard_normal(shape).astype(np.float32)
                x /= np.maximum(np.linalg.norm(x, axis=-1, keepdims=True),
                                1e-9)
                out[name] = jnp.asarray(x)
            elif name == "candidate_mask":
                m = np.ones(shape, bool)
                m[-max(1, shape[0] // 100):] = False   # padded tail
                out[name] = jnp.asarray(m)
            else:
                raise KeyError(f"no smoke generator for {name}")
        return out

    # arg layout is fixed per kind: train=(params, opt, batch, step);
    # retrieval=(batch,); everything else=(params, batch)
    batch_idx = {"train": 2, "retrieval": 0}.get(bundle.kind, 1)
    args = []
    for i, a in enumerate(spec_args):
        if i == batch_idx:
            args.append(batch_arrays(a))
        elif isinstance(a, jax.ShapeDtypeStruct) and a.shape == ():
            args.append(jnp.asarray(0, a.dtype))
        else:
            # params / opt_state tree: materialize via the real init
            args.append(_materialize_tree(bundle, i, key))
    return tuple(args)


def _n_nodes_of(bundle) -> int:
    return next(s.shape[0] for k, s in _find_batch(bundle).items()
                if k in ("node_feat", "atom_z"))


def _n_graphs_of(bundle) -> int:
    return _find_batch(bundle)["energy"].shape[0]


def _find_batch(bundle) -> dict:
    batch_idx = {"train": 2, "retrieval": 0}.get(bundle.kind, 1)
    return bundle.arg_specs[batch_idx]


def _materialize_tree(bundle, arg_idx: int, key):
    """Re-run the real init for params; optimizer init for opt state."""
    arch, cfg = bundle.arch, bundle.model_cfg
    spec = get_arch(arch)
    if spec.family in ("lm", "lm-encoder") or arch == "bert4rec":
        params = tfm.init_params(key, cfg)
    elif spec.family == "gnn":
        params = schnet_m.init_params(key, cfg)
    elif arch == "dlrm-mlperf":
        params = recsys_m.dlrm_init(key, cfg)
    elif arch == "fm":
        params = recsys_m.fm_init(key, cfg)
    elif arch == "wide-deep":
        params = recsys_m.widedeep_init(key, cfg)
    else:
        raise KeyError(arch)
    if arg_idx == 0:
        return params
    opt = adafactor() if bundle.optimizer == "adafactor" else adamw()
    return opt.init(params)

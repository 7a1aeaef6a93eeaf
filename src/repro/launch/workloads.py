"""Cell builder: (architecture x shape x mesh) -> lowerable step + specs.

Every dry-run cell is a ``Workload``: a step function, ShapeDtypeStruct input
templates (no allocation), and in/out shardings for the production mesh.
This module is the single source of truth for how each architecture family
is sharded (DESIGN.md §6) — the trainer, server, and dry-run all build their
jitted steps here.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import ArchSpec, ShapeSpec
from repro.compat import shard_map as compat_shard_map
from repro.launch.mesh import data_axes
from repro.models import recsys as fm_model
from repro.models import transformer as lm
from repro.models.gnn import dimenet as m_dimenet
from repro.models.gnn import egnn as m_egnn
from repro.models.gnn import gatedgcn as m_gatedgcn
from repro.models.gnn import pna as m_pna
from repro.optim import adamw_init, adamw_update, opt_state_shardings

F32, I32 = jnp.float32, jnp.int32


@dataclasses.dataclass
class Workload:
    name: str
    step: Callable
    input_specs: tuple  # positional ShapeDtypeStructs
    in_shardings: tuple
    out_shardings: Any
    model_flops: float  # analytic useful FLOPs (6ND etc.) for §Roofline
    notes: str = ""
    # donated arg positions (params/opt for train, KV cache for decode):
    # the trainer/server donate these, so the dry-run memory analysis must
    # alias them too — otherwise fits-HBM double-counts the state
    donate: tuple = ()


def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


def _ns(mesh, *spec):
    return NamedSharding(mesh, P(*spec))


# ---------------------------------------------------------------------------
# LM family
# ---------------------------------------------------------------------------

def _lm_model_flops(cfg, tokens: int, kind: str, kv_len: int = 0) -> float:
    n = cfg.active_param_count()
    if kind == "train":
        return 6.0 * n * tokens
    if kind == "prefill":
        return 2.0 * n * tokens
    # decode: one token per sequence + attention over the cache
    attn = 4.0 * tokens * kv_len * cfg.n_heads * cfg.d_head
    return 2.0 * n * tokens + attn * cfg.n_layers


def build_lm_cell(spec: ArchSpec, shape: ShapeSpec, mesh) -> Workload:
    cfg = spec.config
    dp = data_axes(mesh)
    dims = shape.dims
    b, s = dims["global_batch"], dims["seq_len"]
    if cfg.is_moe:
        # sort-based MoE dispatch: one token chunk per data shard, experts
        # over the model axis (see models/moe.py)
        n_tok_shards = int(np.prod([mesh.shape[a] for a in dp])) if dp else 1
        cfg = dataclasses.replace(
            cfg, n_token_shards=n_tok_shards, dp_axes=tuple(dp), ep_axis="model"
        )
    pshard = lm.param_shardings(cfg, mesh, dp=dp)
    pshapes = jax.eval_shape(lambda: lm.init_params(jax.random.PRNGKey(0), cfg))
    # Sequence-parallel inter-layer residuals: the (B,S,D) activation saved
    # per layer (remat residual) is sharded (batch -> dp, seq -> model) —
    # without the seq axis the 94-layer stacks of the 235B config need
    # ~484 GiB/device (measured); with SP they drop 16x.  GSPMD inserts the
    # all-gather before attention and the reduce-scatter after (classic SP).
    seq_ok = (s % mesh.shape.get("model", 1) == 0) if "model" in mesh.axis_names else False
    dp_act = _ns(mesh, dp, "model" if seq_ok else None, None)

    if shape.kind == "train":
        oshard = opt_state_shardings(pshard, pshapes, mesh, dp=dp)
        oshapes = jax.eval_shape(adamw_init, pshapes)

        logits_sh = _ns(mesh, dp, None, "model")

        def step(params, opt, tokens, labels):
            loss, grads = jax.value_and_grad(lm.loss_fn)(
                params, cfg, tokens, labels, dp_act, logits_sh
            )
            params, opt, gn = adamw_update(
                params, grads, opt,
                mom_shardings=oshard["mu"], param_shardings=pshard,
            )
            return params, opt, loss, gn

        inputs = (
            pshapes,
            oshapes,
            _sds((b, s), I32),
            _sds((b, s), I32),
        )
        in_sh = (pshard, oshard, _ns(mesh, dp, None), _ns(mesh, dp, None))
        out_sh = (pshard, oshard, _ns(mesh), _ns(mesh))
        flops = _lm_model_flops(cfg, b * s, "train")
        return Workload(f"{spec.name}:{shape.name}", step, inputs, in_sh, out_sh,
                        flops, donate=(0, 1))

    if shape.kind == "prefill":
        def step(params, tokens):
            return lm.prefill(params, cfg, tokens, dp_act)

        inputs = (pshapes, _sds((b, s), I32))
        in_sh = (pshard, _ns(mesh, dp, None))
        cache_sh = {
            "k": _ns(mesh, None, dp, "model", None, None),
            "v": _ns(mesh, None, dp, "model", None, None),
        }
        out_sh = (_ns(mesh, dp, None, "model"), cache_sh)
        flops = _lm_model_flops(cfg, b * s, "prefill")
        return Workload(f"{spec.name}:{shape.name}", step, inputs, in_sh, out_sh, flops)

    # decode: one new token against a seq_len KV cache
    def step(params, cache, token, pos):
        return lm.decode_step(params, cfg, cache, token, pos)

    cache_shape = (cfg.n_layers, b, s, cfg.n_kv, cfg.d_head)
    cache_sds = {"k": _sds(cache_shape, jnp.bfloat16), "v": _sds(cache_shape, jnp.bfloat16)}
    cache_sh = {
        "k": _ns(mesh, None, dp, "model", None, None),  # KV sequence-sharded over TP
        "v": _ns(mesh, None, dp, "model", None, None),
    }
    inputs = (pshapes, cache_sds, _sds((b,), I32), _sds((), I32))
    in_sh = (pshard, cache_sh, _ns(mesh, dp), _ns(mesh))
    out_sh = (_ns(mesh, dp, "model"), cache_sh)
    flops = _lm_model_flops(cfg, b, "decode", kv_len=s)
    return Workload(f"{spec.name}:{shape.name}", step, inputs, in_sh, out_sh,
                    flops, donate=(1,))


# ---------------------------------------------------------------------------
# GNN family
# ---------------------------------------------------------------------------

_GNN_MODULES = {
    "dimenet": m_dimenet,
    "egnn": m_egnn,
    "gatedgcn": m_gatedgcn,
    "pna": m_pna,
}


def _gnn_batch_specs(arch: str, n: int, e: int, d: int, n_graphs: int, n_triplets: int):
    """ShapeDtypeStruct batch for a GNN cell (superset per arch needs)."""
    batch = {
        "x": _sds((n, d), F32),
        "edge_index": _sds((2, e), I32),
    }
    if arch == "gatedgcn":
        batch["edge_attr"] = _sds((e, 1), F32)
    if arch in ("gatedgcn", "pna"):
        batch["labels"] = _sds((n,), I32)
        batch["train_mask"] = _sds((n,), F32)
    if arch in ("egnn", "dimenet"):
        batch["pos"] = _sds((n, 3), F32)
        batch["graph_ids"] = _sds((n,), I32)
        batch["y"] = _sds((n_graphs,), F32)
    if arch == "dimenet":
        batch["z"] = _sds((n,), I32)
        batch["triplets"] = _sds((2, n_triplets), I32)
    return batch


def _gnn_batch_shardings(arch: str, batch_specs: dict, mesh, dp):
    """Edge-parallel: edge-indexed arrays over dp, node arrays replicated
    (psum'd segment reductions)."""
    sh = {}
    for k, v in batch_specs.items():
        if k in ("edge_index", "triplets"):
            sh[k] = _ns(mesh, None, dp)
        elif k == "edge_attr":
            sh[k] = _ns(mesh, dp, None)
        else:
            sh[k] = _ns(mesh, *([None] * len(v.shape)))
    return sh


def build_gnn_cell(spec: ArchSpec, shape: ShapeSpec, mesh) -> Workload:
    arch = spec.name
    mod = _GNN_MODULES[arch]
    dims = shape.dims
    dp = data_axes(mesh)

    if shape.name == "molecule":
        n_graphs = dims["batch"]
        n = dims["n_nodes"] * n_graphs
        e = dims["n_edges"] * n_graphs
        d = 16
    elif shape.name == "minibatch_lg":
        n, e, d = dims["sub_nodes"], dims["sub_edges"], 602
        n_graphs = 1
    else:
        n, e, d = dims["n_nodes"], dims["n_edges"], dims["d_feat"]
        n_graphs = 1
    # edge arrays are sharded over (pod x data); pad to a common multiple —
    # the pipeline pads real batches with zero-weight self-loop edges
    e = (e + 511) // 512 * 512
    n_triplets = min(2 * e, 8_000_000)  # capped triplet sampling (documented)

    cfg = spec.config
    if arch in ("gatedgcn", "pna"):
        cfg = dataclasses.replace(cfg, d_in=d)
    if arch == "egnn":
        cfg = dataclasses.replace(cfg, d_in=d)

    batch_specs = _gnn_batch_specs(arch, n, e, d, n_graphs, n_triplets)
    if arch == "dimenet":
        # z is derived from x in the adapter to keep the x input live
        del batch_specs["x"]
        batch_specs["x"] = _sds((n, d), F32)
    pshapes = jax.eval_shape(lambda: mod.init_params(jax.random.PRNGKey(0), cfg))
    pshard = jax.tree.map(lambda _: _ns(mesh), pshapes)  # replicated (small)

    def loss_adapter(params, batch):
        batch = dict(batch)
        batch["n_graphs"] = n_graphs
        if arch == "dimenet" and "z" not in batch:
            batch["z"] = (
                jnp.abs(batch["x"].sum(-1)).astype(I32) % spec.config.n_species
            )
        if arch == "egnn" and "pos" not in batch:
            batch["pos"] = batch["x"][:, :3]
        return mod.loss_fn(params, cfg, batch)

    oshard = opt_state_shardings(pshard, pshapes, mesh, dp=())
    oshapes = jax.eval_shape(adamw_init, pshapes)

    def step(params, opt, batch):
        loss, grads = jax.value_and_grad(loss_adapter)(params, batch)
        params, opt, gn = adamw_update(params, grads, opt)
        return params, opt, loss, gn

    if arch == "dimenet" and "z" in batch_specs:
        # keep explicit z (molecule pipeline provides it); derive only if absent
        pass

    inputs = (pshapes, oshapes, batch_specs)
    in_sh = (pshard, oshard, _gnn_batch_shardings(arch, batch_specs, mesh, dp))
    out_sh = (pshard, oshard, _ns(mesh), _ns(mesh))

    # analytic FLOPs: edge-dominated message passing
    h = getattr(cfg, "d_hidden", getattr(cfg, "d_hidden", 64))
    depth = getattr(cfg, "n_layers", getattr(cfg, "n_blocks", 4))
    flops = 6.0 * e * h * h * depth
    if arch == "dimenet":
        flops += 6.0 * n_triplets * h * cfg.n_bilinear * depth
    return Workload(f"{spec.name}:{shape.name}", step, inputs, in_sh, out_sh,
                    flops, donate=(0, 1))


# ---------------------------------------------------------------------------
# RecSys family
# ---------------------------------------------------------------------------

def build_recsys_cell(spec: ArchSpec, shape: ShapeSpec, mesh) -> Workload:
    cfg = spec.config
    dp = data_axes(mesh)
    pshard = fm_model.param_shardings(cfg, mesh)
    pshapes = jax.eval_shape(lambda: fm_model.init_params(jax.random.PRNGKey(0), cfg))
    dims = shape.dims

    if shape.kind == "train":
        b = dims["batch"]
        oshard = opt_state_shardings(pshard, pshapes, mesh, dp=dp)
        oshapes = jax.eval_shape(adamw_init, pshapes)

        def step(params, opt, batch):
            loss, grads = jax.value_and_grad(fm_model.loss_fn)(params, cfg, batch)
            params, opt, gn = adamw_update(params, grads, opt)
            return params, opt, loss, gn

        batch_specs = {"ids": _sds((b, cfg.n_fields), I32), "labels": _sds((b,), F32)}
        batch_sh = {"ids": _ns(mesh, dp, None), "labels": _ns(mesh, dp)}
        inputs = (pshapes, oshapes, batch_specs)
        in_sh = (pshard, oshard, batch_sh)
        out_sh = (pshard, oshard, _ns(mesh), _ns(mesh))
        flops = 6.0 * b * cfg.n_fields * cfg.embed_dim
        return Workload(f"{spec.name}:{shape.name}", step, inputs, in_sh, out_sh,
                        flops, donate=(0, 1))

    # Serving shardings (§Perf hillclimb: fm:serve_bulk): the table is
    # read-only at serve time and fits HBM (1.4 GiB f32), so it is
    # REPLICATED — lookups become device-local gathers and the cross-model
    # all-reduce of partial embedding sums (28 MB/step, the dominant term of
    # the baseline) disappears; the batch shards over the WHOLE mesh.
    serve_pshard = jax.tree.map(lambda _: _ns(mesh), pshapes)
    all_axes = tuple(mesh.axis_names)

    if shape.kind == "serve":
        b = dims["batch"]

        def step(params, batch):
            return fm_model.serve_step(params, cfg, batch)

        batch_specs = {"ids": _sds((b, cfg.n_fields), I32)}
        inputs = (pshapes, batch_specs)
        in_sh = (serve_pshard, {"ids": _ns(mesh, all_axes, None)})
        out_sh = _ns(mesh, all_axes)
        flops = 2.0 * b * cfg.n_fields * cfg.embed_dim
        return Workload(f"{spec.name}:{shape.name}", step, inputs, in_sh, out_sh, flops)

    # retrieval: one query vs n_candidates (candidates sharded over the mesh;
    # padded to a mesh-divisible count — the pipeline pads with sentinel rows)
    nc = (dims["n_candidates"] + 511) // 512 * 512

    def step(params, user_ids, cand_rows):
        return fm_model.retrieval_scores(params, cfg, user_ids, cand_rows)

    inputs = (pshapes, _sds((1, cfg.n_fields), I32), _sds((nc,), I32))
    in_sh = (serve_pshard, _ns(mesh, None, None), _ns(mesh, all_axes))
    out_sh = _ns(mesh, all_axes)
    flops = 2.0 * nc * cfg.embed_dim
    return Workload(f"{spec.name}:{shape.name}", step, inputs, in_sh, out_sh, flops)


# ---------------------------------------------------------------------------
# sameAs engine (the paper's workload)
# ---------------------------------------------------------------------------

def build_engine_cell(spec: ArchSpec, shape: ShapeSpec, mesh) -> Workload:
    from repro.core.engine_jax import build_plans, eval_plan, process_candidates
    from repro.core.rules import Rule
    from repro.core.terms import SAME_AS, var

    dims = shape.dims
    cap = dims["capacity"]  # per-device arena rows
    n_res = dims["n_resources"]
    axes = tuple(mesh.axis_names)  # flatten the whole mesh for the engine
    n_dev = int(np.prod([mesh.shape[a] for a in axes]))
    cfg = spec.config
    bind_cap, out_cap, rw_cap = cfg.bind_cap, cfg.out_cap, cfg.rewrite_cap

    # representative 2-atom join rule: <x1', x2, x3> <- <x1,x2,x3> & <x1,~,x1'>
    rule = Rule((var(4), var(2), var(3)), ((var(1), var(2), var(3)), (var(1), SAME_AS, var(4))))
    plan = tuple(build_plans(rule, full=False)[0])
    head_slots = tuple(t if t < 0 else None for t in rule.head)

    def step(spo, epoch, marked, tomb, n_used, rep, sort_perm, sorted_keys,
             pos_perm, pos_keys, atom_consts, head_consts, r):
        heads, valid, n_d, n_a, ov_b, ov_o = eval_plan(
            spo, epoch, marked, tomb, sorted_keys, sort_perm,
            pos_keys, pos_perm, r, atom_consts, head_consts,
            plan=plan, head_var_slots=head_slots,
            bind_cap=bind_cap, out_cap=out_cap, axis=axes,
        )
        return process_candidates(
            spo, epoch, marked, n_used, rep, sort_perm, sorted_keys,
            pos_perm, pos_keys, heads, valid, r,
            rewrite_cap=rw_cap, axis=axes, n_shards=n_dev,
            route_cap=cfg.route_cap,
        )

    smap = compat_shard_map(
        step,
        mesh=mesh,
        in_specs=(P(axes), P(axes), P(axes), P(axes), P(axes), P(),
                  P(axes), P(axes), P(axes), P(axes), P(), P(), P()),
        out_specs=(
            P(axes), P(axes), P(axes), P(axes), P(), P(axes), P(axes),
            P(axes), P(axes),
            {
                "rep_changed": P(), "contradiction": P(),
                "ov_rewrite": P(axes), "ov_store": P(axes), "ov_route": P(axes),
                "ov_pair": P(axes),
                "n_new": P(axes), "n_pairs": P(), "n_marked": P(axes),
                "n_reflexive": P(axes), "delta_rows": P(axes),
                "delta_valid": P(axes),
            },
        ),
    )

    rows = (cap + 1) * n_dev
    inputs = (
        _sds((rows, 3), I32), _sds((rows,), I32), _sds((rows,), jnp.bool_),
        _sds((rows,), I32),
        _sds((n_dev,), I32), _sds((n_res,), I32),
        _sds((rows,), I32), _sds((rows,), jnp.int64),
        _sds((rows,), I32), _sds((rows,), jnp.int64),
        _sds((2, 3), I32), _sds((3,), I32), _sds((), I32),
    )
    in_sh = tuple(
        [_ns(mesh, axes, None), _ns(mesh, axes), _ns(mesh, axes), _ns(mesh, axes),
         _ns(mesh, axes), _ns(mesh), _ns(mesh, axes), _ns(mesh, axes),
         _ns(mesh, axes), _ns(mesh, axes), _ns(mesh), _ns(mesh), _ns(mesh)]
    )
    out_sh = None  # let SPMD infer from shard_map out_specs
    # one round over a full arena: joins ~ sort+search over cap rows/device
    flops = float(n_dev * cap * np.log2(max(cap, 2)) * 8)
    return Workload(
        f"{spec.name}:{shape.name}", smap, inputs, in_sh, out_sh, flops,
        notes="one SPMD materialisation round (join plan + process)",
    )


def build_cell(spec: ArchSpec, shape: ShapeSpec, mesh) -> Workload:
    if spec.family == "lm":
        return build_lm_cell(spec, shape, mesh)
    if spec.family == "gnn":
        return build_gnn_cell(spec, shape, mesh)
    if spec.family == "recsys":
        return build_recsys_cell(spec, shape, mesh)
    if spec.family == "engine":
        return build_engine_cell(spec, shape, mesh)
    raise ValueError(spec.family)

"""Cell builder: (arch x shape x mesh) -> lowering-ready step function.

For each family this module constructs:
  * the jittable step function for the cell kind (train / prefill / decode /
    serve / candidates / encode / search),
  * ShapeDtypeStruct stand-ins for every input (params and optimizer state
    included — nothing is allocated; the shannon/kernels input_specs
    pattern),
  * in/out NamedShardings resolved from the logical-axis specs,
  * MODEL_FLOPS metadata for §Roofline (6·N·D train / 2·N_active·D fwd
    conventions; analytic formulas for GNN/recsys documented inline).

launch/dryrun.py calls `build_cell` then `.lower().compile()`.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Callable, Dict, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from repro.configs.base import ArchSpec, ShapeCell
from repro.dist.sharding import Sharder, is_logical_spec
from repro.models import colpali as colpali_mod
from repro.models import gnn as gnn_mod
from repro.models import recsys as recsys_mod
from repro.models import transformer as T
from repro.optim import optimizer as opt
from repro.retrieval import Query, Retriever

SDS = jax.ShapeDtypeStruct


@dataclasses.dataclass
class BuiltCell:
    arch_id: str
    cell: ShapeCell
    fn: Callable                 # positional args
    args: Tuple[Any, ...]        # ShapeDtypeStructs (pytrees thereof)
    in_shardings: Tuple[Any, ...]
    out_shardings: Any
    donate_argnums: Tuple[int, ...]
    meta: Dict[str, Any]


def _shard_tree(sharder: Sharder, spec_tree, sds_tree):
    return jax.tree.map(
        lambda spec, s: sharder.named(tuple(spec), s.shape),
        spec_tree, sds_tree, is_leaf=is_logical_spec)


def _eval_sds(fn, *args):
    return jax.eval_shape(fn, *args)


def _opt_cfg_for(arch_id: str) -> opt.AdamWConfig:
    if arch_id.startswith("kimi"):
        # 1T params: bf16 params + int8 moments (docs/design.md §6)
        return opt.AdamWConfig(moment_dtype="int8")
    return opt.AdamWConfig()


# ---------------------------------------------------------------------------
# LM family
# ---------------------------------------------------------------------------

def _lm_model_flops(cfg: T.LMConfig, cell: ShapeCell) -> float:
    n_active = cfg.active_param_count()
    d = cell.dims
    if cell.kind == "train":
        tokens = d["global_batch"] * d["seq_len"]
        return 6.0 * n_active * tokens
    if cell.kind == "prefill":
        tokens = d["global_batch"] * d["seq_len"]
        return 2.0 * n_active * tokens
    # decode: one token per sequence per step
    return 2.0 * n_active * d["global_batch"]


def build_lm_cell(spec: ArchSpec, cell: ShapeCell, mesh: Mesh,
                  smoke: bool = False) -> BuiltCell:
    cfg = spec.smoke_config if smoke else spec.config
    sharder = Sharder(mesh)
    dims = cell.dims
    gb, seq = dims["global_batch"], dims["seq_len"]

    params_sds = _eval_sds(lambda: T.init(jax.random.PRNGKey(0), cfg))
    pspecs = T.param_specs(cfg)
    p_sh = _shard_tree(sharder, pspecs, params_sds)
    batch_sh = sharder.named(("batch", None), (gb, seq))
    meta = {"model_flops": _lm_model_flops(cfg, cell),
            "params": cfg.param_count(),
            "active_params": cfg.active_param_count()}

    if cell.kind == "train":
        ocfg = _opt_cfg_for(spec.arch_id)
        opt_sds = _eval_sds(partial(opt.init, ocfg), params_sds)
        ospecs = opt.state_specs(pspecs, ocfg)
        o_sh = _shard_tree(sharder, ospecs, opt_sds)
        fn = lambda p, o, b: T.train_step(p, o, b, cfg, ocfg, shd=sharder)
        batch = {"tokens": SDS((gb, seq), jnp.int32),
                 "targets": SDS((gb, seq), jnp.int32)}
        b_sh = {"tokens": batch_sh, "targets": batch_sh}
        return BuiltCell(spec.arch_id, cell, fn,
                         (params_sds, opt_sds, batch),
                         (p_sh, o_sh, b_sh), (p_sh, o_sh, None), (0, 1),
                         meta)

    if cell.kind == "prefill":
        fn = lambda p, tok: T.prefill(p, tok, cfg, max_len=seq, shd=sharder)
        tok = SDS((gb, seq), jnp.int32)
        cache_sds = _eval_sds(fn, params_sds, tok)[1]
        c_sh = _shard_tree(sharder, T.cache_specs(), cache_sds)
        return BuiltCell(spec.arch_id, cell, fn, (params_sds, tok),
                         (p_sh, batch_sh), (None, c_sh), (), meta)

    # decode: one token against a seq-length cache
    fn = lambda p, tok, cache, pos: T.decode_step(p, tok, cache, pos, cfg,
                                                  shd=sharder)
    tok = SDS((gb,), jnp.int32)
    cache = T.KVCache(
        SDS((cfg.n_layers, gb, seq, cfg.n_kv_heads, cfg.hd), cfg.adtype),
        SDS((cfg.n_layers, gb, seq, cfg.n_kv_heads, cfg.hd), cfg.adtype))
    c_sh = _shard_tree(sharder, T.cache_specs(), cache)
    tok_sh = sharder.named(("batch",), (gb,))
    pos = SDS((), jnp.int32)
    return BuiltCell(spec.arch_id, cell, fn, (params_sds, tok, cache, pos),
                     (p_sh, tok_sh, c_sh, None), (None, c_sh), (2,), meta)


# ---------------------------------------------------------------------------
# GNN family (PNA)
# ---------------------------------------------------------------------------

def _gnn_model_flops(cfg: gnn_mod.PNAConfig, dims: Dict[str, int]) -> float:
    """Analytic PNA step flops: encoder N*2*f*d; per layer: pre-MLP
    E*2*(2d*d), post-MLP N*2*(13d*d); head N*2*d*c. x3 for fwd+bwd."""
    n, e, d = dims["n_nodes"], dims["n_edges"], cfg.d_hidden
    f, c = dims["d_feat"], dims["n_classes"]
    fwd = (2 * n * f * d
           + cfg.n_layers * (2 * e * 2 * d * d + 2 * n * 13 * d * d)
           + 2 * n * d * c)
    return 3.0 * fwd


def build_gnn_cell(spec: ArchSpec, cell: ShapeCell, mesh: Mesh,
                   smoke: bool = False) -> BuiltCell:
    base = spec.smoke_config if smoke else spec.config
    dims = cell.dims
    cfg = dataclasses.replace(
        base, d_feat=dims["d_feat"], n_classes=dims["n_classes"],
        task="graph" if "n_graphs" in dims else "node")
    sharder = Sharder(mesh)
    n, e = dims["n_nodes"], dims["n_edges"]

    params_sds = _eval_sds(lambda: gnn_mod.init(jax.random.PRNGKey(0), cfg))
    pspecs = gnn_mod.param_specs(cfg)
    p_sh = _shard_tree(sharder, pspecs, params_sds)

    batch = {"feats": SDS((n, dims["d_feat"]), jnp.float32),
             "edge_index": SDS((2, e), jnp.int32),
             "labels": SDS((n,), jnp.int32)}
    b_sh = {"feats": sharder.named(("nodes", None), (n, dims["d_feat"])),
            "edge_index": sharder.named((None, "edge"), (2, e)),
            "labels": sharder.named(("nodes",), (n,))}
    if "n_graphs" in dims:
        batch["graph_ids"] = SDS((n,), jnp.int32)
        batch["graph_labels"] = SDS((dims["n_graphs"],), jnp.int32)
        b_sh["graph_ids"] = sharder.named(("nodes",), (n,))
        b_sh["graph_labels"] = sharder.named((None,), (dims["n_graphs"],))
        del batch["labels"], b_sh["labels"]

    meta = {"model_flops": _gnn_model_flops(cfg, dims),
            "params": cfg.param_count()}

    ocfg = opt.AdamWConfig()
    opt_sds = _eval_sds(partial(opt.init, ocfg), params_sds)
    o_sh = _shard_tree(sharder, opt.state_specs(pspecs, ocfg), opt_sds)
    fn = lambda p, o, b: gnn_mod.train_step(p, o, b, cfg, ocfg, shd=sharder)
    return BuiltCell(spec.arch_id, cell, fn, (params_sds, opt_sds, batch),
                     (p_sh, o_sh, b_sh), (p_sh, o_sh, None), (0, 1), meta)


# ---------------------------------------------------------------------------
# RecSys family
# ---------------------------------------------------------------------------

def _recsys_dense_params(params_sds) -> int:
    """Parameters outside the embedding tables (MLPs, cross, GRUs)."""
    total = 0
    for path, leaf in jax.tree_util.tree_flatten_with_path(params_sds)[0]:
        if "tables" not in jax.tree_util.keystr(path):
            total += int(jnp.prod(jnp.array(leaf.shape)))
    return total


def _recsys_batch(cfg: recsys_mod.RecsysConfig, b: int):
    if cfg.family in ("din", "dien"):
        return {"hist_ids": SDS((b, cfg.seq_len), jnp.int32),
                "hist_mask": SDS((b, cfg.seq_len), jnp.bool_),
                "target_ids": SDS((b,), jnp.int32),
                "label": SDS((b,), jnp.float32)}
    return {"dense": SDS((b, cfg.n_dense), jnp.float32),
            "sparse_ids": SDS((b, cfg.n_sparse), jnp.int32),
            "label": SDS((b,), jnp.float32)}


def _recsys_batch_shardings(sharder: Sharder, batch):
    return {k: sharder.named(("batch",) + (None,) * (len(v.shape) - 1),
                             v.shape) for k, v in batch.items()}


def build_recsys_cell(spec: ArchSpec, cell: ShapeCell, mesh: Mesh,
                      smoke: bool = False) -> BuiltCell:
    cfg = spec.smoke_config if smoke else spec.config
    sharder = Sharder(mesh)
    dims = cell.dims

    params_sds = _eval_sds(
        lambda: recsys_mod.init(jax.random.PRNGKey(0), cfg))
    pspecs = recsys_mod.param_specs(cfg)
    p_sh = _shard_tree(sharder, pspecs, params_sds)
    dense_p = _recsys_dense_params(params_sds)
    emb_p = sum(cfg.table_rows) * cfg.embed_dim

    if cell.kind == "candidates":
        nc = dims["n_candidates"]
        if cfg.family in ("din", "dien"):
            one = {"hist_ids": SDS((1, cfg.seq_len), jnp.int32),
                   "hist_mask": SDS((1, cfg.seq_len), jnp.bool_)}
        else:
            one = {"dense": SDS((1, cfg.n_dense), jnp.float32),
                   "sparse_ids": SDS((1, cfg.n_sparse), jnp.int32)}
        cand = SDS((nc,), jnp.int32)
        fn = lambda p, b, c: recsys_mod.score_candidates(p, b, c, cfg,
                                                         shd=sharder)
        one_sh = {k: sharder.named((None,) * len(v.shape), v.shape)
                  for k, v in one.items()}
        cand_sh = sharder.named(("candidate",), (nc,))
        # hist per candidate: attention MLP over seq_len; dense: top MLP
        meta = {"model_flops": 2.0 * dense_p * nc
                * (cfg.seq_len if cfg.family in ("din", "dien") else 1),
                "params": dense_p + emb_p}
        return BuiltCell(spec.arch_id, cell, fn, (params_sds, one, cand),
                         (p_sh, one_sh, cand_sh),
                         sharder.named(("candidate",), (nc,)), (), meta)

    b = dims["batch"]
    batch = _recsys_batch(cfg, b)
    b_sh = _recsys_batch_shardings(sharder, batch)
    seq_mult = cfg.seq_len if cfg.family in ("din", "dien") else 1

    if cell.kind == "serve":
        fn = lambda p, bb: recsys_mod.serve_step(p, bb, cfg, shd=sharder)
        meta = {"model_flops": 2.0 * dense_p * b * seq_mult,
                "params": dense_p + emb_p}
        return BuiltCell(spec.arch_id, cell, fn, (params_sds, batch),
                         (p_sh, b_sh), sharder.named(("batch",), (b,)),
                         (), meta)

    ocfg = opt.AdamWConfig()
    opt_sds = _eval_sds(partial(opt.init, ocfg), params_sds)
    o_sh = _shard_tree(sharder, opt.state_specs(pspecs, ocfg), opt_sds)
    fn = lambda p, o, bb: recsys_mod.train_step(p, o, bb, cfg, ocfg,
                                                shd=sharder)
    meta = {"model_flops": 6.0 * dense_p * b * seq_mult,
            "params": dense_p + emb_p}
    return BuiltCell(spec.arch_id, cell, fn, (params_sds, opt_sds, batch),
                     (p_sh, o_sh, b_sh), (p_sh, o_sh, None), (0, 1), meta)


# ---------------------------------------------------------------------------
# ColPali family (the paper's system)
# ---------------------------------------------------------------------------

def build_colpali_cell(spec: ArchSpec, cell: ShapeCell, mesh: Mesh,
                       smoke: bool = False) -> BuiltCell:
    arch = spec.smoke_config if smoke else spec.config
    enc = arch.encoder
    sharder = Sharder(mesh)
    dims = cell.dims

    params_sds = _eval_sds(
        lambda: colpali_mod.init(jax.random.PRNGKey(0), enc))
    pspecs = colpali_mod.param_specs(enc)
    p_sh = _shard_tree(sharder, pspecs, params_sds)
    n_active = enc.param_count()

    if cell.kind == "train":
        gb = dims["global_batch"]
        batch = {"query_tokens": SDS((gb, enc.query_len), jnp.int32),
                 "query_mask": SDS((gb, enc.query_len), jnp.bool_),
                 "doc_patches": SDS((gb, enc.n_patches, enc.d_patch),
                                    jnp.float32),
                 "doc_mask": SDS((gb, enc.n_patches), jnp.bool_)}
        b_sh = {k: sharder.named(("batch",) + (None,) * (len(v.shape) - 1),
                                 v.shape) for k, v in batch.items()}
        ocfg = opt.AdamWConfig()
        opt_sds = _eval_sds(partial(opt.init, ocfg), params_sds)
        o_sh = _shard_tree(sharder, opt.state_specs(pspecs, ocfg), opt_sds)
        fn = lambda p, o, bb: colpali_mod.train_step(p, o, bb, arch.encoder,
                                                     ocfg, shd=sharder)
        tokens = gb * (enc.query_len + enc.n_patches)
        meta = {"model_flops": 6.0 * n_active * tokens, "params": n_active}
        return BuiltCell(spec.arch_id, cell, fn,
                         (params_sds, opt_sds, batch),
                         (p_sh, o_sh, b_sh), (p_sh, o_sh, None), (0, 1),
                         meta)

    if cell.kind == "encode":
        gb = dims["global_batch"]
        fn = lambda p, pat, m: colpali_mod.encode_doc(p, pat, m, arch.encoder,
                                                      shd=sharder)
        pat = SDS((gb, enc.n_patches, enc.d_patch), jnp.float32)
        msk = SDS((gb, enc.n_patches), jnp.bool_)
        pat_sh = sharder.named(("batch", None, None), pat.shape)
        msk_sh = sharder.named(("batch", None), msk.shape)
        meta = {"model_flops": 2.0 * n_active * gb * enc.n_patches,
                "params": n_active}
        return BuiltCell(spec.arch_id, cell, fn, (params_sds, pat, msk),
                         (p_sh, pat_sh, msk_sh), None, (), meta)

    # search: the corpus-sharded ADC MaxSim scan + global top-k merge, as
    # `Retriever.search_sharded` serves it (no rerank: scan + merge only)
    q_n, n_docs = dims["queries"], dims["corpus"]
    md, mq, d = arch.kept_patches, enc.query_len, enc.proj_dim
    retriever = Retriever(dataclasses.replace(arch.hpc, rerank=0))
    state = retriever.backend.abstract_state(n=n_docs, md=md, d=d,
                                             k=arch.hpc.k)
    st_sh = _shard_tree(sharder, retriever.backend.shard_specs(state), state)
    query = Query(SDS((q_n, mq, d), jnp.float32), SDS((q_n, mq), jnp.bool_),
                  SDS((q_n, mq), jnp.float32))
    q_sh = jax.tree.map(lambda s: sharder.named((None,) * len(s.shape),
                                                s.shape), query)
    fn = lambda st, q: retriever.search_sharded(st, q, k=arch.top_k,
                                                mesh=mesh)
    # ADC scan reads 1 B/code; table build is the only matmul
    meta = {"model_flops": 2.0 * q_n * mq * arch.hpc.k * d
            + 1.0 * q_n * mq * n_docs * md,   # compares (add/max ops)
            "params": arch.hpc.k * d}
    return BuiltCell(spec.arch_id, cell, fn, (state, query), (st_sh, q_sh),
                     None, (), meta)


FAMILY_BUILDERS = {
    "lm": build_lm_cell,
    "gnn": build_gnn_cell,
    "recsys": build_recsys_cell,
    "colpali": build_colpali_cell,
}


def build_cell(spec: ArchSpec, cell: ShapeCell, mesh: Mesh,
               smoke: bool = False) -> BuiltCell:
    return FAMILY_BUILDERS[spec.family](spec, cell, mesh, smoke=smoke)

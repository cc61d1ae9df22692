"""(architecture × input-shape) cell builders for the multi-pod dry-run.

A cell packages everything ``dryrun.py`` needs to lower+compile one entry of
the assignment matrix: a step closure, abstract inputs (ShapeDtypeStruct —
never allocated), and in/out PartitionSpec trees for the given mesh.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs import get_config, get_shapes
from repro.launch import sharding as sh
from repro.launch.mesh import dp_axes


@dataclasses.dataclass
class Cell:
    arch: str
    shape: str
    kind: str
    step_fn: Callable
    abstract_args: Tuple[Any, ...]
    in_specs: Tuple[Any, ...]
    out_specs: Any
    skip: Optional[str] = None
    notes: str = ""


def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


# ===========================================================================
# iCD cells — the paper's own model at production scale
# ===========================================================================
def _icd_cell(arch: str, shape_spec, mesh) -> Cell:
    from repro.core.models import mf
    from repro.sparse.interactions import Interactions

    cfg = get_config(arch)
    dp = dp_axes(mesh)

    if shape_spec.kind == "retrieval":
        n_cand = shape_spec.extra("n_candidates")
        bq = shape_spec.global_batch

        def step(w_users, h_items):
            scores = w_users @ h_items.T
            vals, idx = jax.lax.top_k(scores, 100)
            return vals, idx

        return Cell(
            arch, shape_spec.name, "retrieval", step,
            (_sds((bq, cfg.k), jnp.float32), _sds((n_cand, cfg.k), jnp.float32)),
            (P(dp, None), P("model", None)),
            (P(dp, None), P(dp, None)),
            notes="paper-native separable retrieval: one matvec per query",
        )

    n_ctx = shape_spec.extra("n_ctx")
    n_items = shape_spec.extra("n_items")
    nnz = shape_spec.extra("nnz")
    # unroll=True: exact HLO cost accounting (XLA counts while bodies once)
    # and better cross-column pipelining on TPU
    hp = mf.MFHyperParams(k=cfg.k, alpha0=cfg.alpha0, l2=cfg.l2, unroll=True)

    params_abs = mf.MFParams(
        w=_sds((n_ctx, cfg.k), jnp.float32),
        h=_sds((n_items, cfg.k), jnp.float32),
    )
    data_abs = Interactions(
        ctx=_sds((nnz,), jnp.int32), item=_sds((nnz,), jnp.int32),
        y=_sds((nnz,), jnp.float32), alpha=_sds((nnz,), jnp.float32),
        t_ctx=_sds((nnz,), jnp.int32), t_item=_sds((nnz,), jnp.int32),
        t_perm=_sds((nnz,), jnp.int32),
        indptr=_sds((n_ctx + 1,), jnp.int32),
        t_indptr=_sds((n_items + 1,), jnp.int32),
        n_ctx=n_ctx, n_items=n_items,
    )
    e_abs = _sds((nnz,), jnp.float32)

    p_specs, d_spec_dict = sh.icd_mf_specs(mesh)
    data_specs = Interactions(
        ctx=d_spec_dict["ctx"], item=d_spec_dict["item"], y=d_spec_dict["y"],
        alpha=d_spec_dict["alpha"], t_ctx=d_spec_dict["t_ctx"],
        t_item=d_spec_dict["t_item"], t_perm=d_spec_dict["t_perm"],
        indptr=d_spec_dict["indptr"], t_indptr=d_spec_dict["t_indptr"],
        n_ctx=n_ctx, n_items=n_items,
    )

    def step(params, data, e):
        return mf.epoch(params, data, e, hp)

    return Cell(
        arch, shape_spec.name, "train", step,
        (params_abs, data_abs, e_abs),
        (p_specs, data_specs, P(dp)),
        (p_specs, P(dp)),
        notes="one full iCD epoch; cross-shard traffic = k² Gram all-reduce",
    )


# ===========================================================================
# registry
# ===========================================================================
# The seed-template LM/RecSys/GNN cell builders left with the unused
# architecture zoo (PR 8 retirement); only the paper's own iCD archs exist.
ICD_ARCHS = ("icd-mf",)


def all_cell_ids(include_icd: bool = True):
    out = []
    for arch in ICD_ARCHS if include_icd else ():
        for shape_name in get_shapes(arch):
            out.append((arch, shape_name))
    return out


def build_cell(arch: str, shape_name: str, mesh, cfg_override=None,
               probe: bool = False, shape_override=None) -> Cell:
    shape_spec = shape_override or get_shapes(arch)[shape_name]
    if arch in ICD_ARCHS or arch.startswith("icd"):
        return _icd_cell(arch, shape_spec, mesh)
    raise KeyError(arch)

"""Sharding rules: optimizer/train-state PartitionSpecs and the iCD specs.

Conventions (DESIGN.md §5):
  * batch/context dims shard over ``dp`` = ("pod","data") on multi-pod,
    ("data",) on single-pod;
  * weights shard over "model" on their parallel dim and over "data" on the
    other large dim (ZeRO/FSDP via GSPMD all-gather-at-use). Parameters are
    intentionally NOT sharded over "pod": cross-pod traffic is the gradient
    all-reduce only;
  * embedding / vocab tables row-shard over "model";
  * small vectors (norms, biases) replicate.
"""
from __future__ import annotations

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.launch.mesh import dp_axes


def named(mesh, spec_tree):
    return jax.tree_util.tree_map(
        lambda spec: NamedSharding(mesh, spec),
        spec_tree,
        is_leaf=lambda x: isinstance(x, P),
    )


def _drop_data(spec: P) -> P:
    """Replace every 'data'/('data',) entry with None (ZeRO-1 live params:
    replicated over data, sharded over model only)."""
    def clean(e):
        if e == "data" or e == ("data",):
            return None
        return e

    return P(*[clean(e) for e in spec])


# ------------------------------------------------------------- optimizer --
def opt_state_specs(param_specs):
    """AdamW state: m/v mirror the parameters, step replicates."""
    return {"step": P(), "m": param_specs, "v": param_specs}


def train_state_specs(param_specs):
    from repro.train.train_step import TrainState

    return TrainState(params=param_specs, opt=opt_state_specs(param_specs),
                      step=P())


def zero1_state_specs(fsdp_param_specs):
    """ZeRO-1 TrainState specs: live (bf16) params lose the 'data' axis;
    the fp32 master + adam moments inside the optimizer keep it."""
    from repro.train.train_step import TrainState

    live = jax.tree_util.tree_map(
        _drop_data, fsdp_param_specs,
        is_leaf=lambda x: isinstance(x, P),
    )
    opt = {"master": fsdp_param_specs,
           "inner": opt_state_specs(fsdp_param_specs)}
    return TrainState(params=live, opt=opt, step=P()), live


# ------------------------------------------------------------------ icd ---
def icd_mf_specs(mesh):
    """W rows (contexts) over dp; H rows (items) over model; observation
    arrays over dp; the run offsets replicate. The k×k Grams replicate —
    Lemma 2's k² all-reduce."""
    dp = dp_axes(mesh)
    from repro.core.models.mf import MFParams

    params = MFParams(w=P(dp, None), h=P("model", None))
    data = dict(
        ctx=P(dp), item=P(dp), y=P(dp), alpha=P(dp),
        t_ctx=P(dp), t_item=P(dp), t_perm=P(dp), indptr=P(), t_indptr=P(),
    )
    return params, data

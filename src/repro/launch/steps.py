"""Step builders: the train, prefill and decode steps of a model under a
``(data, model)`` mesh, with the shardings of every argument.

Each builder returns ``(fn, abstract_args, in_shardings, donate_argnums)``:
``launch/dryrun.py`` compiles them from the abstract arguments on
placeholder devices, and ``chip_smoke.py --chips 4`` runs the train step on
real ones.  Importing this module sets nothing.
"""
import os
from dataclasses import replace

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from ..models import registry
from ..models.config import InputShape, ModelConfig
from ..sharding.specs import tree_shardings, use_sharding
from ..train.loop import TrainConfig, make_train_step
from . import inputs as I
from .mesh import mesh_axis_size


def build_train(cfg: ModelConfig, shape: InputShape, mesh, rules):
    cfg = replace(cfg, remat=True)   # layer-granularity activation ckpt
    # sequence-parallel residual storage (Korthikanti et al. '22): the
    # between-block activations shard their seq dim over the model axis so
    # per-layer checkpoints are not replicated across TP ranks.
    if os.environ.get("REPRO_SP_RESIDUAL", "1") == "1" and shape.seq_len % 16 == 0:
        rules = rules.with_(seq="model")
    step = make_train_step(cfg, TrainConfig())
    batch_specs = I.batch_specs(cfg, shape)
    params = registry.abstract_params(cfg)
    opt = {"mu": params, "nu": params, "step": jax.ShapeDtypeStruct((), jnp.int32)}
    logical = registry.logical_axes(cfg)
    p_sh = tree_shardings(mesh, rules, logical)
    # ZeRO-1: moments shard their embed dim over data even when params
    # stay replicated across the data axis.
    opt_rules = rules.with_(embed_fsdp="data") \
        if cfg.d_model % mesh_axis_size(mesh, "data") == 0 else rules
    m_sh = tree_shardings(mesh, opt_rules, logical)
    o_sh = {"mu": m_sh, "nu": m_sh,
            "step": NamedSharding(mesh, P())}
    b_logical = I.batch_logical(cfg, shape)
    b_sh = {k: NamedSharding(mesh, rules.spec_for(v))
            for k, v in b_logical.items()}

    def fn(params, opt_state, batch):
        with use_sharding(mesh, rules):
            return step(params, opt_state, batch)

    return fn, (params, opt, batch_specs), (p_sh, o_sh, b_sh), (0, 1)


def build_prefill(cfg: ModelConfig, shape: InputShape, mesh, rules):
    batch_specs = I.batch_specs(cfg, shape)
    params = registry.abstract_params(cfg)
    logical = registry.logical_axes(cfg)
    p_sh = tree_shardings(mesh, rules, logical)
    b_logical = I.batch_logical(cfg, shape)
    b_sh = {k: NamedSharding(mesh, rules.spec_for(v))
            for k, v in b_logical.items()}

    def fn(params, batch):
        with use_sharding(mesh, rules):
            logits, _ = registry.forward(params, cfg, batch)
            return logits

    return fn, (params, batch_specs), (p_sh, b_sh), ()


def build_decode(cfg: ModelConfig, shape: InputShape, mesh, rules):
    # tiny global batches (long_500k B=1) cannot shard over data
    data_total = mesh_axis_size(mesh, "data") * mesh_axis_size(mesh, "pod")
    if shape.global_batch % data_total:
        rules = rules.with_(batch=None)
    # SPerf iteration (hillclimb): when KV heads cannot shard over the model
    # axis, shard the cache *sequence* dim instead (ring-context parallel) —
    # otherwise the KV cache replicates across all 16 TP ranks.
    if os.environ.get("REPRO_DECODE_SEQ_SHARD", "0") == "1":
        rules = rules.with_(kv_seq="model")
    cache, tok, pos = I.decode_specs(cfg, shape)
    params = registry.abstract_params(cfg)
    logical = registry.logical_axes(cfg)
    p_sh = tree_shardings(mesh, rules, logical)
    c_logical = I.cache_logical(cfg)
    c_sh = tree_shardings(mesh, rules, c_logical)
    t_sh = NamedSharding(mesh, rules.spec_for(("batch", None)))
    s_sh = NamedSharding(mesh, P())

    def fn(params, cache, token, pos):
        with use_sharding(mesh, rules):
            return registry.decode_step(params, cfg, cache, token, pos)

    return fn, (params, cache, tok, pos), (p_sh, c_sh, t_sh, s_sh), (1,)


BUILDERS = {"train": build_train, "prefill": build_prefill,
            "decode": build_decode}

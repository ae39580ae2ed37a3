"""Train-step builders: the GSPMD step (production) and an explicit
shard_map DDP step (gradient-compression path).

``make_train_step(cfg)`` returns a pure function
    (params, opt_state, batch, step) -> (params, opt_state, metrics)
ready for ``jax.jit`` with in/out shardings from launch/sharding.py.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from ..models import transformer as model
from . import compress as compress_mod
from .optimizer import OptHyper, clip_by_global_norm, get_optimizer

Params = Any


def make_train_step(cfg, hyper: OptHyper = OptHyper(), *,
                    attn_chunk: int = 1024, skip_upper_triangle: bool = True):
    opt = get_optimizer(cfg.optimizer)

    def train_step(params, opt_state, batch, step):
        def lf(p):
            return model.loss_fn(p, cfg, batch, chunk=attn_chunk,
                                 skip_upper_triangle=skip_upper_triangle)

        (loss, metrics), grads = jax.value_and_grad(lf, has_aux=True)(params)
        grads, gnorm = clip_by_global_norm(grads, hyper.clip_norm)
        params, opt_state = opt.update(params, grads, opt_state, step, hyper)
        metrics = dict(metrics, loss=loss, grad_norm=gnorm)
        return params, opt_state, metrics

    return train_step


def init_train_state(cfg, key):
    params = model.init_params(cfg, key)
    opt = get_optimizer(cfg.optimizer)
    return params, opt.init(params)


# ---------------------------------------------------------------------------
# explicit DDP (shard_map) with optional int8 gradient compression
# ---------------------------------------------------------------------------


def make_ddp_step(cfg, mesh, hyper: OptHyper = OptHyper(), *,
                  axis: str = "data", compress: bool = False,
                  attn_chunk: int = 1024):
    """Pure data parallelism with an explicit gradient psum.

    Demonstrates the compression trick end-to-end (params replicated, batch
    sharded over ``axis``); the production path uses GSPMD instead.
    """
    from jax.sharding import PartitionSpec as P

    opt = get_optimizer(cfg.optimizer)

    @functools.partial(
        jax.shard_map, mesh=mesh,
        in_specs=(P(), P(), P(axis), P(), P()),
        out_specs=(P(), P(), P(), P()),
        check_vma=False,
    )
    def ddp_step(params, opt_state, batch, step, residuals):
        def lf(p):
            return model.loss_fn(p, cfg, batch, chunk=attn_chunk)

        (loss, metrics), grads = jax.value_and_grad(lf, has_aux=True)(params)
        if compress:
            grads, residuals = compress_mod.compressed_psum(grads, residuals,
                                                            axis)
        else:
            grads = jax.tree.map(lambda g: jax.lax.pmean(g, axis), grads)
        grads, gnorm = clip_by_global_norm(grads, hyper.clip_norm)
        params, opt_state = opt.update(params, grads, opt_state, step, hyper)
        loss = jax.lax.pmean(loss, axis)
        return params, opt_state, loss, residuals

    return ddp_step

"""Step factories: jitted train / prefill / decode with explicit shardings.

Each factory returns a ``StepBundle``: the jitted fn, ShapeDtypeStruct trees
for every argument (what the dry-run lowers against), and the NamedShardings.
The real trainer/server uses the same bundle and feeds concrete arrays.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from ..train.optimizer import OptConfig, OptState, adamw_update, init_opt_state
from . import model as M
from .config import ModelConfig
from .sharding import batch_spec, cache_specs, dp_axes, param_specs, to_shardings


class TrainState(NamedTuple):
    params: Any
    opt: OptState


@dataclasses.dataclass
class StepBundle:
    fn: Callable  # jitted step
    arg_shapes: tuple  # ShapeDtypeStruct trees (lower(*arg_shapes))
    arg_shardings: tuple
    out_shardings: Any
    init: Optional[Callable] = None  # builds real initial state


def _named(specs, mesh):
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s), specs, is_leaf=lambda x: isinstance(x, P)
    )


def _batch_shardings(batch_shapes: dict, mesh: Mesh) -> dict:
    out = {}
    for k, v in batch_shapes.items():
        out[k] = NamedSharding(mesh, batch_spec(mesh, v.shape[0], len(v.shape)))
    return out


# ---------------------------------------------------------------------------
# train
# ---------------------------------------------------------------------------


def make_train_step(
    cfg: ModelConfig,
    mesh: Mesh,
    batch_shapes: dict,
    opt_cfg: OptConfig = OptConfig(),
    remat: str = "full",
    accum: int = 1,
    seed: int = 0,
) -> StepBundle:
    key = jax.random.PRNGKey(seed)
    param_shapes = jax.eval_shape(lambda k: M.init_params(k, cfg), key)
    opt_shapes = jax.eval_shape(lambda p: init_opt_state(p, opt_cfg), param_shapes)
    state_shapes = TrainState(params=param_shapes, opt=opt_shapes)

    pspecs = param_specs(param_shapes, cfg, mesh)
    mspecs = param_specs(opt_shapes.m, cfg, mesh)
    vspecs = param_specs(opt_shapes.v, cfg, mesh)
    state_specs = TrainState(
        params=pspecs, opt=OptState(m=mspecs, v=vspecs, step=P())
    )
    state_sh = _named(state_specs, mesh)
    batch_sh = _batch_shardings(batch_shapes, mesh)

    def step(state: TrainState, batch: dict):
        _ctx = jax.sharding.use_abstract_mesh(mesh.abstract_mesh)
        _ctx.__enter__()
        if accum > 1:
            def micro(c, mb):
                (l, (ce, aux)), g = jax.value_and_grad(
                    lambda p: M.loss_fn(p, cfg, mb, remat), has_aux=True
                )(state.params)
                gsum, lsum = c
                return (jax.tree.map(jnp.add, gsum, g), lsum + l), None

            mb = jax.tree.map(
                lambda a: a.reshape((accum, a.shape[0] // accum) + a.shape[1:]), batch
            )
            zero = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), state.params
            )
            (gsum, lsum), _ = jax.lax.scan(micro, (zero, jnp.float32(0.0)), mb)
            grads = jax.tree.map(lambda g: g / accum, gsum)
            loss = lsum / accum
        else:
            (loss, (ce, aux)), grads = jax.value_and_grad(
                lambda p: M.loss_fn(p, cfg, batch, remat), has_aux=True
            )(state.params)
        new_p, new_opt, om = adamw_update(state.params, grads, state.opt, opt_cfg)
        metrics = {"loss": loss, **om}
        _ctx.__exit__(None, None, None)
        return TrainState(params=new_p, opt=new_opt), metrics

    fn = jax.jit(
        step,
        in_shardings=(state_sh, batch_sh),
        out_shardings=(state_sh, None),
        donate_argnums=(0,),
    )

    def init() -> TrainState:
        params = jax.jit(
            lambda k: M.init_params(k, cfg), out_shardings=_named(pspecs, mesh)
        )(key)
        opt = jax.jit(
            lambda p: init_opt_state(p, opt_cfg),
            out_shardings=_named(OptState(m=mspecs, v=vspecs, step=P()), mesh),
        )(params)
        return TrainState(params=params, opt=opt)

    return StepBundle(
        fn=fn,
        arg_shapes=(state_shapes, batch_shapes),
        arg_shardings=(state_sh, batch_sh),
        out_shardings=(state_sh, None),
        init=init,
    )


# ---------------------------------------------------------------------------
# prefill
# ---------------------------------------------------------------------------


def make_prefill_step(
    cfg: ModelConfig,
    mesh: Mesh,
    batch_shapes: dict,
    s_max: int,
    cache_dtype=jnp.bfloat16,
    seed: int = 0,
) -> StepBundle:
    key = jax.random.PRNGKey(seed)
    param_shapes = jax.eval_shape(lambda k: M.init_params(k, cfg), key)
    pspecs = param_specs(param_shapes, cfg, mesh)
    params_sh = _named(pspecs, mesh)
    batch_sh = _batch_shardings(batch_shapes, mesh)
    B = next(iter(batch_shapes.values())).shape[0]

    cache_shapes = jax.eval_shape(
        lambda: M.init_cache(cfg, B, s_max, cache_dtype)
    )
    cspecs = cache_specs(cache_shapes, cfg, mesh, B)
    cache_sh = _named(cspecs, mesh)

    def step(params, batch):
        with jax.sharding.use_abstract_mesh(mesh.abstract_mesh):
            cache = M.init_cache(cfg, B, s_max, cache_dtype)
            logits, cache = M.prefill(params, cfg, batch, cache)
            return logits, cache

    fn = jax.jit(
        step,
        in_shardings=(params_sh, batch_sh),
        out_shardings=(NamedSharding(mesh, batch_spec(mesh, B, 3)), cache_sh),
    )
    return StepBundle(
        fn=fn,
        arg_shapes=(param_shapes, batch_shapes),
        arg_shardings=(params_sh, batch_sh),
        out_shardings=(None, cache_sh),
    )


# ---------------------------------------------------------------------------
# decode
# ---------------------------------------------------------------------------


def make_decode_step(
    cfg: ModelConfig,
    mesh: Mesh,
    batch: int,
    s_max: int,
    cache_dtype=jnp.bfloat16,
    seed: int = 0,
) -> StepBundle:
    key = jax.random.PRNGKey(seed)
    param_shapes = jax.eval_shape(lambda k: M.init_params(k, cfg), key)
    pspecs = param_specs(param_shapes, cfg, mesh)
    params_sh = _named(pspecs, mesh)

    cache_shapes = jax.eval_shape(lambda: M.init_cache(cfg, batch, s_max, cache_dtype))
    cspecs = cache_specs(cache_shapes, cfg, mesh, batch)
    cache_sh = _named(cspecs, mesh)

    if cfg.input_mode == "frames":
        tok_shape = jax.ShapeDtypeStruct((batch, 1, cfg.d_model), jnp.bfloat16)
    else:
        tok_shape = jax.ShapeDtypeStruct((batch, 1), jnp.int32)
    tok_sh = NamedSharding(mesh, batch_spec(mesh, batch, len(tok_shape.shape)))
    len_shape = jax.ShapeDtypeStruct((), jnp.int32)
    len_sh = NamedSharding(mesh, P())

    def step(params, cache, tokens, cache_len):
        with jax.sharding.use_abstract_mesh(mesh.abstract_mesh):
            logits, new_cache = M.decode_step(params, cfg, tokens, cache, cache_len)
            return logits, new_cache

    fn = jax.jit(
        step,
        in_shardings=(params_sh, cache_sh, tok_sh, len_sh),
        out_shardings=(NamedSharding(mesh, batch_spec(mesh, batch, 3)), cache_sh),
        donate_argnums=(1,),
    )
    return StepBundle(
        fn=fn,
        arg_shapes=(param_shapes, cache_shapes, tok_shape, len_shape),
        arg_shardings=(params_sh, cache_sh, tok_sh, len_sh),
        out_shardings=(None, cache_sh),
    )

"""The program under test, as a configuration file names it."""
from __future__ import annotations

import math
from typing import Dict

import numpy as np


def program_config(config: Dict):
    """The program's model config: its registry entry for
    ``program_arch`` with ``program_overrides`` applied."""
    from repro.configs import get_config
    return get_config(config["program_arch"]).with_(
        **config.get("program_overrides", {}))


def published_dt_bias(config: Dict, n_heads: int) -> np.ndarray:
    """Mamba2's initial ``dt_bias``: dt log-uniform between
    ``time_step_min`` and ``time_step_max``, here at the midpoint
    quantiles of that range, one per head; the bias is dt's inverse
    softplus."""
    lo = math.log(config["time_step_min"])
    hi = math.log(config["time_step_max"])
    dt = np.exp(lo + (hi - lo) * (np.arange(n_heads) + 0.5) / n_heads)
    return (dt + np.log(-np.expm1(-dt))).astype(np.float32)


def published_init(config: Dict, params, opt):
    """The program's initial state with what the configuration states and
    the program's initialisation does not: Mamba2's ``dt_bias`` where the
    configuration gives the dt range.  Weights and the f32 master copy
    alike, on the device, in their own shardings."""
    if "time_step_min" not in config:
        return params, opt
    import jax
    import jax.numpy as jnp

    def fix(path, x):
        if getattr(path[-1], "key", None) != "dt_bias":
            return x
        return jnp.broadcast_to(
            jnp.asarray(published_dt_bias(config, x.shape[-1]), x.dtype),
            x.shape)

    def apply(tree):
        shard = jax.tree.map(lambda x: x.sharding, tree)
        return jax.jit(lambda t: jax.tree_util.tree_map_with_path(fix, t),
                       out_shardings=shard, donate_argnums=0)(tree)

    return apply(params), dict(opt, master=apply(opt["master"]))

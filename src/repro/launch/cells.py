"""Cell construction shared by the dry-run, train and serve drivers:
input specs, model build, lowering per (config, mesh).

Importable WITHOUT touching jax device state (unlike launch.dryrun, whose
first lines force 512 host devices -- that module is only for the dry-run
process itself).
"""

from typing import Any, Dict

import jax
import jax.numpy as jnp

from repro import obs
from repro.compile import REGISTRY
from repro.configs import EinetConfig
from repro.core import EiNet, Normal, poon_domingos, random_binary_trees
from repro.core.exponential_family import make_exponential_family
from repro.core.em import EMConfig, stochastic_em_update
from repro.dist import sharding as shlib


def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


def input_specs(cfg: EinetConfig, shape_spec=None) -> Dict[str, Any]:
    """ShapeDtypeStruct stand-ins for every model input of a cell."""
    d = (cfg.height * cfg.width * cfg.num_channels
         if cfg.structure == "pd" else cfg.num_vars)
    return {"x": _sds((cfg.batch_size, d), jnp.float32)}


def build_einet(cfg: EinetConfig, impl: str = "xla") -> EiNet:
    """The registered config's EiNet; ``impl`` picks the log-einsum-exp
    path ("xla" or the "pallas" kernels)."""
    if cfg.structure == "pd":
        graph = poon_domingos(
            cfg.height, cfg.width, cfg.delta, cfg.num_channels, cfg.pd_axes
        )
    else:
        graph = random_binary_trees(cfg.num_vars, cfg.depth, cfg.num_repetitions)
    if cfg.exponential_family == "normal":
        ef = Normal(min_var=cfg.min_var, max_var=cfg.max_var)
    elif cfg.exponential_family == "binomial":
        # 8-bit image data modelled as counts, the paper's MNIST treatment
        ef = make_exponential_family("binomial", n_trials=255)
    elif cfg.exponential_family == "categorical":
        ef = make_exponential_family("categorical", num_categories=256)
    else:
        raise ValueError(
            f"{cfg.name}: unsupported leaf family {cfg.exponential_family!r}"
        )
    return EiNet(graph, num_sums=cfg.num_sums, num_classes=cfg.num_classes,
                 exponential_family=ef, impl=impl)


def lower_einet_cell(cfg: EinetConfig, mesh, multi_pod: bool):
    rules = shlib.default_rules(multi_pod, fsdp=False)
    model = build_einet(cfg)
    with shlib.use_rules(rules):
        params_struct = jax.eval_shape(
            lambda: model.init(jax.random.PRNGKey(0))
        )
        param_sh = shlib.tree_shardings(mesh, params_struct)
        batch_struct = input_specs(cfg)
        batch_sh = shlib.batch_shardings(mesh, batch_struct)

        def fn(p, batch):
            # one distributed stochastic-EM step: E-step statistics are summed
            # over the DP axes by XLA (they are grads of the summed batch LL)
            return stochastic_em_update(model, p, batch["x"], EMConfig())

        jitted = REGISTRY.jit(
            model,
            ("lowered_cell", cfg.name, multi_pod),
            fn,
            jit_kwargs={
                "in_shardings": (param_sh, batch_sh),
                "out_shardings": (param_sh, None),
            },
        )
        with obs.timed("compile.lower", arch=cfg.name) as t:
            lowered = jitted.lower(params_struct, batch_struct)
        return lowered, t.seconds, model

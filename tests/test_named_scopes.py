"""The compiled EM step names its layers: every device op carries the HLO
``op_name`` of the named scope it came from (``em.estep``, ``einet.leaf``,
``plan.<kind>`` per executed plan segment, ``em.leaf_stats``,
``em.mstep``), which a profiler trace reports per op."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import EinetConfig
from repro.core.em import em_statistics
from repro.launch.cells import build_einet
from repro.train import TrainConfig, make_em_step

TINY = {
    "rat": EinetConfig(name="scopes-rat", structure="rat", num_vars=32,
                       depth=2, num_repetitions=2, num_sums=4, batch_size=16),
    "pd": EinetConfig(name="scopes-pd", structure="pd", height=4, width=8,
                      num_channels=1, delta=2, pd_axes=("h", "w"),
                      num_sums=4, batch_size=16),
}


def _model(arch):
    model = build_einet(TINY[arch])
    params = model.init(jax.random.PRNGKey(0))
    x = jnp.asarray(np.random.RandomState(0).randn(
        16, model.num_vars).astype(np.float32))
    return model, params, x


@pytest.mark.parametrize("arch,kinds", [("rat", {"fused"}),
                                        ("pd", {"gather", "layer"})])
def test_em_step_ops_carry_layer_scopes(arch, kinds):
    model, params, x = _model(arch)
    assert {s.kind for s in model.exec_plan} == kinds
    step = make_em_step(model, TrainConfig(donate=False))
    hlo = step.lower(params, x).compile().as_text()
    names = " ".join(re.findall(r'op_name="([^"]*)"', hlo))
    scopes = ["em.estep", "einet.leaf", "em.leaf_stats", "em.mstep"]
    scopes += [f"plan.{k}" for k in kinds]
    for scope in scopes:
        assert re.search(rf"(^|[/( ]){re.escape(scope)}([/)]|$| )", names), scope
    # the segments' backward runs under the same scope
    for k in kinds:
        assert f"transpose(jvp(plan.{k}))" in names


def test_statistics_psum_is_scoped():
    model, params, x = _model("rat")
    mesh = jax.make_mesh((1,), ("data",))
    fn = jax.shard_map(lambda p, xb: em_statistics(model, p, xb, ("data",)),
                       mesh=mesh, in_specs=(P(), P("data")), out_specs=P(),
                       check_vma=False)
    text = jax.jit(fn).lower(params, x).as_text(debug_info=True)
    assert re.search(r'loc\("[^"]*/em\.allreduce/psum"', text)

"""Compile the main-path programs for a described TPU v5e, with no chip.

The TPU compiler is installed with jax, and it compiles for a chip that is
described (``jax.experimental.topologies``) rather than attached.  What it
refuses here -- a block shape off the (8, 128) tiling, an in-kernel value
gather, a working set over VMEM -- it would refuse on the chip, so these
tests guard every Pallas kernel at the registered widths and the XLA EM step
of einet-pd-svhn.  Nothing runs: a passing compile says nothing about
results or speed (``chip_smoke.py`` checks those on a chip).

The topology is described inside a fixture, never at import: only one
process may load the TPU library at a time, and every pytest-xdist worker
imports this module.  Code that asks ``jax.default_backend()`` (kernel
dispatch, buffer donation) is steered to "tpu" with ``monkeypatch``.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.compile import ProgramRegistry
from repro.configs import get_config
from repro.kernels import ops
from repro.launch.cells import build_einet
from repro.train import TrainConfig, make_em_step

# per-chip batch of each arch: the config batch, except einet-rat-large
# whose 65536 is pod-global (256 rows on each of 256 chips)
PER_CHIP_BATCH = {"einet_rat_large": 256}

# (arch, segment kind): every kernel family at the published widths --
# PD gather segments at K=40 / K=32, the PD root layer, the whole fused
# einet-rat chain at K=10, and one fused group plus the root layer of
# einet-rat-large at K=64
KERNEL_CASES = [
    ("einet_pd", "gather"),
    ("einet_pd", "layer"),
    ("einet_pd_mnist", "gather"),
    ("einet_pd_mnist", "layer"),
    ("einet_rat", "fused"),
    ("einet_rat_large", "fused"),
    ("einet_rat_large", "layer"),
]

V5E_HBM_BYTES = 16 * 2 ** 30


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 -- any failure means "no TPU lib"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache off around them
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture
def tpu_backend(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def _sds(shape, sharding):
    return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=sharding)


def _segment_op(arch, kind, sharding):
    """The ops-level custom-VJP op of the arch's first ``kind`` segment and
    its operand shapes (unpadded: the op applies the padding contract)."""
    cfg = get_config(arch)
    model = build_einet(cfg)
    b = PER_CHIP_BATCH.get(arch, cfg.batch_size)
    k = model.K
    seg = next(s for s in model.exec_plan if s.kind == kind)
    specs = model.pair_specs[seg.start: seg.stop]
    ws = tuple(
        _sds((sp.num_partitions, sp.k_out, k, k), sharding) for sp in specs
    )
    if kind == "fused":
        x = _sds((b, 2 * specs[0].num_partitions, k), sharding)

        def op(ws, x):
            return ops.grouped_log_einsum_exp(seg.out_block, seg.block_b, ws, x)

        return op, (ws, x)
    if kind == "gather":
        vs = tuple(
            _sds((sp.num_mixed, sp.mix_child_local.shape[1], k), sharding)
            for sp in specs if sp.mix_global is not None
        )
        x = _sds((b, seg.tables.num_in_rows, k), sharding)

        def op(ws, vs, x):
            return ops.gather_grouped_log_einsum_exp(
                seg.tables, seg.block_b, ws, vs, x
            )

        return op, (ws, vs, x)
    ln = _sds((b, specs[0].num_partitions, k), sharding)
    return ops.log_einsum_exp, (ws[0], ln, ln)


@pytest.mark.parametrize("direction", ["fwd", "bwd"])
@pytest.mark.parametrize("arch,kind", KERNEL_CASES)
def test_kernel_compiles_for_v5e(arch, kind, direction, one_chip,
                                 tpu_backend):
    op, args = _segment_op(arch, kind, one_chip)
    if direction == "fwd":
        fn = op
    else:
        argnums = tuple(range(len(args)))
        fn = jax.grad(lambda *a: jnp.sum(op(*a)), argnums=argnums)
    compiled = jax.jit(fn).lower(*args).compile()
    hlo = compiled.as_text()
    # the Mosaic kernel is in the program (not the interpreter's XLA ops)
    assert "tpu_custom_call" in hlo
    assert f"_{direction}" in hlo
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < V5E_HBM_BYTES


def test_pd_svhn_em_step_compiles_for_v5e(one_chip, tpu_backend):
    """The XLA EM step at the published width and config batch (512): the
    step chip_smoke.py trains, with its parameters donated as on the chip."""
    cfg = get_config("einet_pd")
    model = build_einet(cfg)
    params = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))
    params = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        params,
    )
    d = cfg.height * cfg.width * cfg.num_channels
    x = _sds((cfg.batch_size, d), one_chip)
    step = make_em_step(model, TrainConfig(), registry=ProgramRegistry())
    compiled = step.lower(params, x).compile()
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < V5E_HBM_BYTES
    # donation survived: the parameter buffers are aliased to the outputs
    assert mem.alias_size_in_bytes > 0

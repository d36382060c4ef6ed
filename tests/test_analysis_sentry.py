"""Recompile-sentry tests: the PR 3 weak-type regression class, the
bounded serve compile count, and the leak detectors themselves.

"One compile per (kind, bucket)" is only an invariant if something can
measure compiles; these tests pin both directions -- the healthy paths
compile exactly once per program, and the seeded leaks (weak-typed prior,
dtype drift) are detected and attributed.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis.sentry import CompileSentry
from repro.compile import ProgramRegistry
from repro.core import EiNet, Normal, random_binary_trees
from repro.serve import ServeEngine, mixed_requests
from repro.train import TrainConfig, make_em_step


@pytest.fixture()
def small_net():
    g = random_binary_trees(8, 2, 2, seed=0)
    net = EiNet(g, num_sums=3, exponential_family=Normal())
    return net, net.init(jax.random.PRNGKey(0))


# ---------------------------------------------------- weak-type regression
def test_em_step_compiles_exactly_once(small_net, compile_sentry):
    """The PR 3 regression: params built by ``init`` (strong float32
    class_prior) run 3 compiled EM steps with EXACTLY one compile."""
    net, params = small_net
    x = jnp.asarray(np.random.RandomState(0).randn(16, net.num_vars),
                    jnp.float32)
    raw = make_em_step(net, TrainConfig(), registry=ProgramRegistry())
    step = compile_sentry.wrap(raw, name="em_step")
    for _ in range(3):
        params, ll = step(params, x)
    compile_sentry.assert_max_compiles(1, name="em_step")
    assert len(compile_sentry.signatures("em_step")) == 1
    compile_sentry.assert_no_leaks()
    assert np.isfinite(float(ll))


def test_weak_typed_prior_detected(small_net, compile_sentry):
    """Seed the bug: a weak-typed class_prior splits the jit cache after
    the first update (the update emits a strong-typed prior), and the
    sentry both counts the second compile and names the leak."""
    net, params = small_net
    params = dict(params)
    # the pre-PR-3 construction: no dtype= -> weak_type=True
    params["class_prior"] = jnp.full(
        (net.num_classes,), 1.0 / net.num_classes)
    assert jax.typeof(params["class_prior"]).weak_type
    x = jnp.asarray(np.random.RandomState(0).randn(16, net.num_vars),
                    jnp.float32)
    raw = make_em_step(net, TrainConfig(), registry=ProgramRegistry())
    step = compile_sentry.wrap(raw, name="em_step")
    for _ in range(3):
        params, _ = step(params, x)
    assert compile_sentry.compiles("em_step") == 2  # the silent recompile
    kinds = {f.kind for f in compile_sentry.findings}
    assert "weak-type-arg" in kinds  # flagged already at the first call
    assert "weak-type-leak" in kinds  # and attributed after the second
    with pytest.raises(AssertionError, match="recompile sentry"):
        compile_sentry.assert_max_compiles(1, name="em_step")
    with pytest.raises(AssertionError, match="weak"):
        compile_sentry.assert_no_leaks()


def test_dtype_promotion_leak_detected(compile_sentry):
    f = compile_sentry.wrap(lambda v: v + 1, name="f")
    f(jnp.zeros((4,), jnp.float32))
    f(jnp.zeros((4,), jnp.int32))
    assert compile_sentry.compiles("f") == 2
    assert any(f_.kind == "dtype-promotion-leak"
               for f_ in compile_sentry.findings)


def test_shape_polymorphism_is_not_a_leak(compile_sentry):
    """Different shapes (bucketing) are legitimate distinct programs."""
    f = compile_sentry.wrap(lambda v: v, name="f")
    f(jnp.zeros((4,), jnp.float32))
    f(jnp.zeros((8,), jnp.float32))
    assert compile_sentry.compiles("f") == 2
    assert compile_sentry.findings == []


# ------------------------------------------------------------ serve stream
def test_mixed_serve_stream_bounded_compiles(small_net):
    """64 mixed-kind requests compile at most kinds x buckets programs --
    the bounded-AOT-cache claim as a sentry invariant, not a cache-size
    check."""
    net, params = small_net
    engine = ServeEngine(net, params, max_batch=8,
                         registry=ProgramRegistry())
    reqs = mixed_requests(net.num_vars, 64, seed=7)
    kinds = {r.kind for r in reqs}
    with CompileSentry(registry=engine.registry) as sentry:
        results = engine.run(reqs)
    assert len(results) == 64
    bound = len(kinds) * len(engine.buckets)
    assert 0 < sentry.registry_compiles() <= bound
    # a second identical wave reuses every program: zero new compiles
    with CompileSentry(registry=engine.registry) as sentry2:
        engine.run(mixed_requests(net.num_vars, 64, seed=8))
    assert sentry2.registry_compiles() == 0


def test_registry_required_for_registry_compiles():
    with CompileSentry() as sentry:
        pass
    with pytest.raises(ValueError, match="registry"):
        sentry.registry_compiles()

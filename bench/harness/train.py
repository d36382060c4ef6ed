"""EM training cells: the user's loop over the config's loader.

One chip: ``repro.train.fit`` (stochastic EM, ``TrainConfig`` defaults) fed
by ``launch.train.einet_loader``: a host batch per step, its copy to the
device, and a sync on the step's mean LL.  Several chips:
``make_sharded_em_step`` on a (data=chips, model=1) mesh, fed as
``launch/train.py --dist-em`` feeds it (``jnp.asarray`` of the global
batch, then the step, then ``float(ll)``).

Set-up builds that one step, drives it from the seed through its first
three steps on the loader's first three batches, and hands the same step
and state to the window.  ``correct`` compares those three steps with the
reference (see :func:`check`).  In a traced run set-up also hands the
tracer the step's compiled HLO text: on one chip that of the registry's
``make_em_step(model, TrainConfig())``, the program ``fit`` runs, lowered
for the cell's batch; on several, that of the sharded step.
"""

from __future__ import annotations

import time
from typing import Dict, Iterable, List

from harness import adapter, core, data, work

WARM_STEPS = 3
REF_BLOCK = 512


def make_runner(model, cell: core.Cell, devs):
    """The path the window drives, built once: (params, batches) ->
    (params, [mean LL per step]); the context it runs in; and (params) ->
    the compiled HLO text of its step at the cell's batch."""
    import jax
    import jax.numpy as jnp

    from repro.train import TrainConfig, fit, make_em_step

    x = jax.ShapeDtypeStruct((cell.traffic["batch"], model.num_vars), jnp.float32)

    if cell.chips == 1:
        def run(params, batches):
            return fit(model, params, batches, TrainConfig())

        def program(params):
            return make_em_step(model, TrainConfig()).lower(params, x).compile().as_text()

        return run, core.no_op, program

    from repro.dist import sharding as shlib
    from repro.launch.mesh import make_mesh_for
    from repro.train import make_sharded_em_step

    mesh = make_mesh_for(devs, model_parallel=1)
    rules = shlib.default_rules(multi_pod=False, fsdp=False)

    def context():
        import contextlib

        stack = contextlib.ExitStack()
        stack.enter_context(shlib.use_rules(rules))
        stack.enter_context(jax.set_mesh(mesh))
        return stack

    with context():
        step = make_sharded_em_step(model, TrainConfig(donate=False, health=False), mesh)

    def run(params, batches):
        lls = []
        for batch in batches:
            params, ll = step(params, jnp.asarray(batch["x"]))
            lls.append(float(ll))
        return params, lls

    def program(params):
        return step.lower(params, x).compile().as_text()

    return run, context, program


def run(cell: core.Cell, seed: int, seconds: float, tracer: core.Tracer,
        counter: core.CompileCounter, t_process: float, devs) -> Dict:
    import jax

    from repro.launch import cells as cells_lib
    from repro.launch import train as train_cli

    cfg, tr = cell.config, cell.traffic
    words = data.seed_words(seed, 2)
    core.log(f"set-up: imports and devices done at {time.perf_counter() - t_process:.2f} s")
    model = cells_lib.build_einet(core.program_config(cfg))
    refmod = cell.reference()
    graph = refmod.graph_for(cfg)
    ref = refmod.Reference(graph, cfg["num_sums"], cfg["num_classes"], cfg["min_var"],
                           cfg["max_var"])
    layout = adapter.Layout(model, ref)
    core.log(f"set-up: model and weight layout done at {time.perf_counter() - t_process:.2f} s")
    r0, params = core.make_weights(ref, layout, words[0])
    jax.block_until_ready(params)
    core.log(f"set-up: weights done at {time.perf_counter() - t_process:.2f} s")
    rows = data.rows(cfg, tr["rows"], words[1])
    loader = train_cli.einet_loader(rows, tr["batch"])
    runner, context, program = make_runner(model, cell, devs)

    with context():
        # the first steps: compile, and the states the reference follows
        snaps = [jax.device_get(params)]
        lls: List[float] = []
        params, ll = runner(params, [loader.batch_at(0)])
        lls += ll
        snaps.append(jax.device_get(params))
        core.log(f"set-up: data and first step done at {time.perf_counter() - t_process:.2f} s")
        params, ll = runner(params, [loader.batch_at(s) for s in range(1, WARM_STEPS)])
        lls += ll
        snaps.append(jax.device_get(params))
        setup_s = time.perf_counter() - t_process
        core.log(f"set-up: data and {WARM_STEPS} steps done at {setup_s:.2f} s")
        if tracer.on:
            tracer.add_program(program(params))
            core.log(f"set-up: scopes of {len(tracer.op_scopes)} ops at "
                     f"{time.perf_counter() - t_process:.2f} s")

        # the window
        stats = {"input_s": 0.0, "steps": 0, "ends": []}
        tracer.start()
        t0 = time.perf_counter()
        tracer.open_window()
        deadline = t0 + seconds

        def batches() -> Iterable[dict]:
            step = WARM_STEPS
            while time.perf_counter() < deadline:
                tracer.tick()
                with core.span("bench.loader"):
                    t = time.perf_counter()
                    batch = loader.batch_at(step)
                    stats["input_s"] += time.perf_counter() - t
                step += 1
                stats["steps"] += 1
                with core.span("bench.step"):
                    yield batch
                stats["ends"].append(time.perf_counter())

        counter.active = True
        params, window_lls = runner(params, batches())
        jax.block_until_ready(params)
        t_end = time.perf_counter()
        counter.active = False
        tracer.stop()
    window_s = t_end - t0
    memory = core.memory_peak(devs)
    del params
    ends = [e - t0 for e in stats["ends"]]
    core.log("steps per second of the window:",
             [sum(1 for e in ends if i <= e < i + 1) for i in range(int(seconds) + 1)])

    t_ref = time.perf_counter()
    checks = check(cell, ref, layout, r0, loader, lls, snaps)
    core.log(f"reference and comparison: {time.perf_counter() - t_ref:.2f} s")
    w = work.counts(graph, cfg["num_sums"], cfg["num_classes"])
    steps = len(window_lls)
    rows_per_s = steps * tr["batch"] / window_s
    return {
        "kind": "train",
        "e2e": {"train_examples_per_s": rows_per_s, "setup_s": setup_s},
        "window_s": window_s,
        "steps": steps,
        "rows_per_s": rows_per_s,
        "input_ms_per_step": 1e3 * stats["input_s"] / max(stats["steps"], 1),
        "work": w,
        "batch": tr["batch"],
        "memory_peak_bytes": memory,
        "attempted": steps,
        "failed": 0 if all(v == v for v in window_lls) else 1,
        "checks": checks,
    }


def check(cell: core.Cell, ref, layout, r0, loader, lls: List[float], snaps) -> core.Checks:
    """The reference follows the first three steps from the same weights
    (``r0``, made by the benchmark) and batches, on the host CPU, whose
    float32 arithmetic is exact to rounding.  Compared, each by its worst
    case:

    * ``ll_gap``: each step's mean LL, |prog - ref| / |ref|;
    * ``mstep_gap``: the first step's M-step estimate, worked out from the
      state after one step, (p1 - (1 - lam) p0) / lam, by the gap of norms
      per parameter tensor;
    * ``change_gap``: the change p3 - p0 after three steps, by the gap of
      norms per parameter tensor.

    Gaps of norms are over the larger of the reference's norm of the
    tensor and the median tensor's.  Tensors whose reference step-1 update
    is under a thousandth of the median tensor's (a single class prior) are
    left out of both.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    lam = cell.traffic["step_size"]
    alpha, floor = cell.traffic["laplace_alpha"], cell.traffic["stat_floor"]
    cpu = jax.devices("cpu")[0]
    with jax.default_device(cpu), jax.default_matmul_precision("highest"):
        stats_fn = jax.jit(ref.statistics)
        m_step = jax.jit(lambda t: ref.m_step(t, alpha, floor))
        blend = jax.jit(lambda old, new: ref.blend(old, new, lam))
        place = jax.jit(layout.to_program)
        r = jax.device_put(r0, cpu)
        r0_prog = jax.device_get(place(r))
        ref_lls, mini1 = [], None
        for s in range(WARM_STEPS):
            x = np.asarray(loader.batch_at(s)["x"])
            total = None
            for i in range(0, len(x), REF_BLOCK):
                st = stats_fn(r, jax.device_put(x[i:i + REF_BLOCK], cpu))
                total = st if total is None else jax.tree_util.tree_map(jnp.add, total, st)
            ref_lls.append(float(total["ll"]) / len(x))
            mini = m_step(total)
            if mini1 is None:
                mini1 = jax.device_get(place(mini))
            r = blend(r, mini)
        r3_prog = jax.device_get(place(r))

    def tree(f, *ts):
        return jax.tree_util.tree_map(lambda *a: f(*[np.asarray(x, np.float64) for x in a]), *ts)

    p0, p1, p3 = snaps
    m_prog = tree(lambda a, b: (b - (1 - lam) * a) / lam, p0, p1)
    d_prog = tree(lambda a, b: b - a, p0, p3)
    d_ref = tree(lambda a, b: b - a, r0_prog, r3_prog)
    upd = core.leaf_norms(tree(lambda a, b: a - b, mini1, r0_prog))
    med = float(np.median(upd))
    keep = [u >= 1e-3 * med for u in upd]
    checks = core.Checks(cell.limits)
    checks.add("ll_gap", max(abs(a - b) / abs(b) for a, b in zip(lls, ref_lls)))
    checks.add("mstep_gap", max(core.norm_gaps(m_prog, mini1, keep)))
    checks.add("change_gap", max(core.norm_gaps(d_prog, d_ref, keep)))
    core.log("train check: program LL", lls, "reference LL", ref_lls,
             "tensors left out", keep.count(False))
    return checks

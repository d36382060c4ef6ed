"""Bring-up smoke test: the main path of this repo, run on TPU chips.

    python chip_smoke.py             # one chip: phases (a)-(f) below
    python chip_smoke.py --chips 4   # four chips: data-parallel EM only

Everything runs in this one process, which owns the chip(s), through the
entry points a user calls (``build_einet``, ``make_em_step``,
``ft.run_training``, ``ServeEngine``).  Phases on one chip:

  (a) device       print the devices; exit non-zero unless they are TPUs
  (b) train        einet-pd-svhn at its config batch (512), a few EM steps
                   through the fault-tolerant loop on procedural data
  (c) precision    the trained model's per-row LL from the main-path
                   programs against the same forward at highest precision
  (d) serve        a mixed request stream through ServeEngine, gated on
                   engine-vs-direct parity as ``launch/serve.py`` is
                   (relative to each value's magnitude)
  (e) pallas       einet-pd-svhn and einet-rat built with the Pallas
                   kernels: forward LL and E-step statistics against the
                   highest-precision XLA path; every program must hold the
                   compiled kernel (``tpu_custom_call``), not the interpreter
  (f) result       the last line, only when every phase passed

``--chips 4`` runs data-parallel EM (``make_sharded_em_step`` on a
("data", "model") = (4, 1) mesh) against ``make_em_step`` on one of those
chips, on the same global batch.

Lines before the last are smoke output (diffs, timings, memory), labelled
``[smoke]``; they are not benchmark metrics.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time
import traceback

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

SEED = 0
TRAIN_STEPS = 5
DP_STEPS = 3
SERVE_REQUESTS = 8  # one pass over serve.workload.DEFAULT_MIX: all 6 kinds

# A row's LL is a sum of ~D leaf terms and a log-sum per circuit node, all in
# float32.  Two float32 programs that differ only in how XLA or a kernel
# associates those sums agree to a few hundred ulps of the row's magnitude at
# most; 1e-5 * (1 + |ll|) is ~84 ulps.  One bf16 pass (8 mantissa bits, the
# TPU default for an f32 matmul) misses it by orders of magnitude.
LL_RTOL = 1e-5
# E-step statistics are batch sums of per-row terms, each within LL_RTOL-like
# relative error; per tensor, 1e-4 * (1 + max|ref|) leaves 10x headroom for
# the longer reductions (batch 512 / 2048) without admitting a bf16 pass.
STAT_RTOL = 1e-4
# data-parallel EM: psum'd per-shard statistics re-associate the batch sum,
# then the M-step normalizes; tests/test_dist.py pins the same 1e-4 on CPU.
DP_RTOL = 1e-4


def say(*parts):
    print("[smoke]", *parts, flush=True)


class SmokeFailure(RuntimeError):
    pass


def check(ok, msg):
    if not ok:
        raise SmokeFailure(msg)


def rel_diff(a, b):
    """max over leaves of max|a - b| / (1 + max|b|)."""
    out = 0.0
    leaves = jax.tree_util.tree_leaves
    for la, lb in zip(leaves(a), leaves(b)):
        la, lb = np.asarray(la, np.float64), np.asarray(lb, np.float64)
        if la.size:
            d = float(np.max(np.abs(la - lb)))
            out = max(out, d / (1.0 + float(np.max(np.abs(lb)))))
    return out


def row_rel_diff(a, b):
    """max over rows of |a - b| / (1 + |b|)."""
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.max(np.abs(a - b) / (1.0 + np.abs(b))))


def highest(fn):
    """The reference: ``fn`` jitted and traced with every contraction at
    highest precision, whatever ``core.layers.PRECISION`` (the main path's
    choice) says -- it is unset while the reference traces, so the context
    decides."""
    from repro.core import layers

    jitted = jax.jit(lambda *args: fn(*args))

    def run(*args):
        main_path = layers.PRECISION
        layers.PRECISION = None
        try:
            with jax.default_matmul_precision("highest"):
                return jitted(*args)
        finally:
            layers.PRECISION = main_path

    return run


def log_memory():
    stats = jax.devices()[0].memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    if peak is not None:
        say(f"device 0 peak_bytes_in_use {peak} "
            f"({peak / 2 ** 30:.3f} GiB)")


# --------------------------------------------------------------------------
# phases
# --------------------------------------------------------------------------
def device_phase(chips):
    devs = jax.devices()
    say("devices:", ", ".join(f"{d.platform}:{d.device_kind}:{d.id}"
                              for d in devs))
    if devs[0].platform != "tpu":
        print(f"chip_smoke: no TPU (jax.devices()[0] is {devs[0].platform})",
              file=sys.stderr)
        sys.exit(1)
    if len(devs) < chips:
        print(f"chip_smoke: --chips {chips} but {len(devs)} TPU devices",
              file=sys.stderr)
        sys.exit(1)


def train_phase(cfg):
    """``repro.launch.train``'s single-model path: build_einet ->
    make_em_step -> ft.run_training, fresh checkpoint dir."""
    from repro.checkpoint import CheckpointManager
    from repro.data import datasets as ds_lib
    from repro.dist import fault_tolerance as ft
    from repro.dist import sharding as shlib
    from repro.launch import cells
    from repro.launch import train as train_cli
    from repro.launch.mesh import make_mesh_for
    from repro.train import TrainConfig, make_em_step

    mesh = make_mesh_for(model_parallel=1)
    rules = shlib.default_rules(multi_pod=False, fsdp=False)
    lls = []
    with shlib.use_rules(rules), jax.set_mesh(mesh):
        model = cells.build_einet(cfg)
        params = model.init(jax.random.PRNGKey(SEED))
        data = train_cli.einet_train_data(cfg, "synthetic",
                                          ds_lib.DEFAULT_DATA_DIR)
        loader = train_cli.einet_loader(data, cfg.batch_size)
        step_jit = make_em_step(model, TrainConfig(donate=False, health=False))

        def step_fn(state, batch):
            t0 = time.perf_counter()
            p, ll = step_jit(state["params"], jnp.asarray(batch["x"]))
            ll = float(ll)
            say(f"train step {int(state['step'])}: mean ll {ll!r} "
                f"({(time.perf_counter() - t0) * 1e3:.1f} ms host clock)")
            return {"params": p, "step": state["step"] + 1, "last_ll": ll}

        init = {"params": params, "step": jnp.zeros((), jnp.int32),
                "last_ll": 0.0}
        with tempfile.TemporaryDirectory() as ckpt:
            state, stats = ft.run_training(
                step_fn, init, loader.batch_at, CheckpointManager(ckpt),
                TRAIN_STEPS, ft.LoopConfig(checkpoint_every=TRAIN_STEPS),
                on_step=lambda s, st: lls.append(st["last_ll"]),
            )
    say(f"train: {cfg.name} batch {cfg.batch_size}, lls {lls}, "
        f"restarts {stats['restarts']}")
    check(stats["restarts"] == 0,
          f"the loop recovered from failures: {stats['failures']}")
    check(len(lls) == TRAIN_STEPS and all(np.isfinite(lls)),
          f"non-finite or missing LL: {lls}")
    check(lls[-1] > lls[0], f"objective did not rise: {lls[0]} -> {lls[-1]}")
    x = jnp.asarray(loader.batch_at(TRAIN_STEPS)["x"])
    return model, state["params"], x, step_jit


def precision_phase(model, params, x, step_jit):
    ll = jax.jit(model.log_likelihood)(params, x)
    ll_ref = highest(model.log_likelihood)(params, x)
    d_rows = row_rel_diff(ll, ll_ref)
    _, step_ll = step_jit(params, x)
    d_step = row_rel_diff(step_ll, jnp.mean(ll_ref))
    leaf = jax.jit(model.leaf_rows)(params, x)
    leaf_ref = highest(model.leaf_rows)(params, x)
    say(f"precision: per-row LL rel diff {d_rows!r} (tol {LL_RTOL}), "
        f"EM-step mean LL rel diff {d_step!r}, leaf rows max abs diff "
        f"{float(jnp.max(jnp.abs(leaf - leaf_ref)))!r}, "
        f"ll range [{float(jnp.min(ll_ref))!r}, {float(jnp.max(ll_ref))!r}]")
    check(bool(jnp.all(jnp.isfinite(ll))), "non-finite per-row LL")
    check(d_rows <= LL_RTOL, f"per-row LL off by {d_rows} > {LL_RTOL}")
    check(d_step <= LL_RTOL, f"EM-step LL off by {d_step} > {LL_RTOL}")


def serve_phase(model, params):
    from repro import serve as serve_lib
    from repro.launch import serve as serve_cli

    reqs = serve_lib.mixed_requests(model.num_vars, SERVE_REQUESTS, seed=SEED)
    report = serve_lib.run_benchmark(model, params, reqs, reps=1)
    for line in serve_lib.format_report(report).splitlines():
        say("serve:", line)
    check(set(report["kinds"]) == set(serve_lib.DEFAULT_MIX),
          f"kinds served: {report['kinds']}")
    check(report["parity_max_rel_diff"] <= serve_cli.PARITY_RTOL,
          f"engine/direct parity {report['parity_max_rel_diff']} > "
          f"{serve_cli.PARITY_RTOL} relative")


def pallas_phase(cfg, params, x):
    from repro.core.em import em_statistics
    from repro.launch import cells

    m_xla = cells.build_einet(cfg)
    m_pl = cells.build_einet(cfg, impl="pallas")
    if params is None:
        params = m_xla.init(jax.random.PRNGKey(SEED))
    progs = (
        ("forward LL", lambda m: m.log_likelihood, row_rel_diff, LL_RTOL),
        ("E-step stats", lambda m: (lambda p, x: em_statistics(m, p, x)),
         rel_diff, STAT_RTOL),
    )
    for name, fn, diff, tol in progs:
        t0 = time.perf_counter()
        compiled = jax.jit(fn(m_pl)).lower(params, x).compile()
        t_compile = time.perf_counter() - t0
        kernels = compiled.as_text().count("tpu_custom_call")
        out = jax.block_until_ready(compiled(params, x))
        ref = highest(fn(m_xla))(params, x)
        d = diff(out, ref)
        say(f"pallas {cfg.name} {name}: {kernels} tpu_custom_call, rel diff "
            f"{d!r} (tol {tol}), compile {t_compile:.1f} s")
        check(kernels > 0, f"{cfg.name} {name}: no compiled Pallas kernel")
        check(all(bool(jnp.all(jnp.isfinite(a)))
                  for a in jax.tree_util.tree_leaves(out)),
              f"{cfg.name} {name}: non-finite output")
        check(d <= tol, f"{cfg.name} {name}: rel diff {d} > {tol}")


def data_parallel_phase(cfg):
    """Sharded EM on a (4, 1) mesh vs the one-chip step, same batches."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.data import datasets as ds_lib
    from repro.dist import sharding as shlib
    from repro.launch import cells
    from repro.launch import train as train_cli
    from repro.launch.mesh import make_mesh_for
    from repro.train import TrainConfig, make_em_step, make_sharded_em_step

    devs = jax.devices()[:4]
    mesh = make_mesh_for(devs, model_parallel=1)
    say(f"mesh {dict(mesh.shape)}")
    model = cells.build_einet(cfg)
    params0 = model.init(jax.random.PRNGKey(SEED))
    data = train_cli.einet_train_data(cfg, "synthetic",
                                      ds_lib.DEFAULT_DATA_DIR)
    loader = train_cli.einet_loader(data, cfg.batch_size)
    tcfg = TrainConfig(donate=False, health=False)
    batches = [np.asarray(loader.batch_at(s)["x"]) for s in range(DP_STEPS)]

    one = make_em_step(model, tcfg)
    p1 = jax.device_put(params0, devs[0])
    lls1 = []
    for s, xb in enumerate(batches):
        t0 = time.perf_counter()
        p1, ll = one(p1, jax.device_put(xb, devs[0]))
        lls1.append(float(ll))
        say(f"one chip step {s}: mean ll {lls1[-1]!r} "
            f"({(time.perf_counter() - t0) * 1e3:.1f} ms host clock)")

    x_sh = NamedSharding(mesh, P("data"))
    with shlib.use_rules(shlib.default_rules(False, fsdp=False)), \
            jax.set_mesh(mesh):
        dp = make_sharded_em_step(model, tcfg, mesh)
        hlo = dp.lower(params0, jax.device_put(batches[0], x_sh)).compile()
        collectives = hlo.as_text().count("all-reduce")
        p4, lls4 = params0, []
        for s, xb in enumerate(batches):
            t0 = time.perf_counter()
            p4, ll = dp(p4, jax.device_put(xb, x_sh))
            lls4.append(float(ll))
            say(f"4-chip step {s}: mean ll {lls4[-1]!r} "
                f"({(time.perf_counter() - t0) * 1e3:.1f} ms host clock)")
    d_params = rel_diff(p4, p1)
    d_ll = row_rel_diff(lls4, lls1)
    say(f"data-parallel: {collectives} all-reduce in the sharded program, "
        f"params rel diff {d_params!r}, LL rel diff {d_ll!r} "
        f"(tol {DP_RTOL})")
    check(collectives > 0, "the sharded step holds no all-reduce")
    check(all(np.isfinite(lls4)), f"non-finite LL: {lls4}")
    check(d_params <= DP_RTOL, f"params rel diff {d_params} > {DP_RTOL}")
    check(d_ll <= DP_RTOL, f"LL rel diff {d_ll} > {DP_RTOL}")


# --------------------------------------------------------------------------
def run_phases(chips):
    from repro.configs import get_config

    failed = []

    def phase(name, fn, *args):
        say(f"phase {name}: start")
        t0 = time.perf_counter()
        try:
            out = fn(*args)
        except Exception:  # noqa: BLE001 -- report every phase, fail at end
            traceback.print_exc()
            failed.append(name)
            say(f"phase {name}: FAILED")
            return None
        say(f"phase {name}: ok ({time.perf_counter() - t0:.1f} s)")
        return out

    pd = get_config("einet_pd")
    if chips == 4:
        phase("data-parallel EM", data_parallel_phase, pd)
    else:
        trained = phase("train", train_phase, pd)
        if trained is None:
            failed += ["precision", "serve", "pallas"]
        else:
            model, params, x, step_jit = trained
            phase("precision", precision_phase, model, params, x, step_jit)
            phase("serve", serve_phase, model, params)
            phase("pallas einet-pd-svhn", pallas_phase, pd, params, x)
        rat = get_config("einet_rat")
        x_rat = jnp.asarray(np.random.RandomState(SEED).randn(
            rat.batch_size, rat.num_vars).astype(np.float32))
        phase("pallas einet-rat", pallas_phase, rat, None, x_rat)
    log_memory()
    return failed


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args()
    device_phase(args.chips)
    from repro.launch import compile_cache

    say("compile cache:", compile_cache.enable())
    failed = run_phases(args.chips)
    if failed:
        print(f"chip_smoke: failed phases: {failed}", file=sys.stderr)
        sys.exit(1)
    dev = jax.devices()[0]
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))


if __name__ == "__main__":
    main()

"""Production training driver: ``--arch`` selects a registered EiNet config,
builds the mesh, installs sharding rules, and runs the fault-tolerant loop
with sharded data, checkpointing, and restart.

On real hardware this runs under ``jax.distributed.initialize()`` with one
process per host; on this container it runs the same code path on however
many devices exist (``--devices`` lets CI exercise the multi-device path via
XLA_FLAGS).

  PYTHONPATH=src python -m repro.launch.train --arch einet_rat --steps 50
"""

from __future__ import annotations

import argparse
import os

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.checkpoint import CheckpointManager
from repro.configs import EinetConfig, get_config
from repro.core import plan as plan_lib
from repro.data import datasets as ds_lib
from repro.data import synthetic
from repro.data.pipeline import ShardedLoader
from repro.dist import fault_tolerance as ft
from repro.dist import sharding as shlib
from repro.launch import cells as dr
from repro.launch.mesh import dp_shards, make_mesh_for
from repro.obs import health as health_lib
from repro.train import (
    TrainConfig,
    make_em_step,
    make_sharded_em_step,
    record_step,
    run_step,
)

# --smoke: the CI trace-smoke profile -- a RAT shape small enough to train
# in seconds on CPU but deep enough to depth-group, with health telemetry
# forced on so the trace/metrics gates see train.health.* populated
SMOKE_CONFIG = EinetConfig(
    name="einet-rat-train-launch-smoke",
    structure="rat",
    num_vars=32,
    depth=2,
    num_repetitions=2,
    num_sums=4,
    batch_size=64,
)


def einet_loader(
    data: np.ndarray,
    global_batch: int,
    num_shards: int = 1,
    shard_id: int = 0,
    start_step: int = 0,
) -> ShardedLoader:
    """Deterministic EiNet loader: shard ``sh`` of step ``s`` reads the
    contiguous row block ``[(s * num_shards + sh) * n, ...)`` (mod data), so
    shards within a step are DISJOINT and steps tile the dataset.

    Delegates to ``repro.data.datasets.array_loader`` (the scheme moved there
    with the image datasets); this name stays as the launch-facing alias the
    disjointness regression test pins (tests/test_train.py -- the pre-PR-3
    inline lambda ignored its shard argument, silently shrinking the
    effective batch num_shards-fold).
    """

    return ds_lib.array_loader(
        data, global_batch, num_shards=num_shards, shard_id=shard_id,
        start_step=start_step,
    )


def einet_train_data(cfg: EinetConfig, dataset: str, data_dir: str) -> np.ndarray:
    """Resolve the EiNet training array for ``--dataset``.

    "synthetic" keeps the pre-image-workbench behaviour (mixture images for
    PD structures, white noise for RAT).  "mnist"/"svhn" load the real
    dataset (npz cache -> download), falling back to the deterministic
    procedural generator on offline hosts so the driver always runs; the
    chosen source is printed so logs record what was actually trained on.
    """
    d = (cfg.height * cfg.width * cfg.num_channels
         if cfg.structure == "pd" else cfg.num_vars)
    if dataset == "synthetic":
        if cfg.structure == "pd":
            # round the proxy width UP so the slice always covers d (the
            # old floor-division under-generated for d not divisible by 48,
            # e.g. einet_pd_mnist's 784 -> 768-dim batches -> shape error)
            return synthetic.gaussian_mixture_images(
                4096, 16, -(-d // 48), 3, seed=0
            )[:, :d]
        return np.random.RandomState(0).randn(4096, d).astype(np.float32)
    try:
        ds = ds_lib.load_image_dataset(dataset, data_dir=data_dir)
    except ds_lib.DatasetUnavailable as e:
        print(f"[train] {e}; using the procedural fallback")
        ds = ds_lib.load_image_dataset(dataset, data_dir=data_dir,
                                       source="procedural")
    print(f"[train] dataset {dataset} ({ds.source}): "
          f"{len(ds.train_x)} train rows")
    data, _ = ds_lib.to_domain(ds.train_x, cfg.exponential_family)
    if data.shape[1] != d:
        raise SystemExit(
            f"--dataset {dataset} has {data.shape[1]} dims but --arch "
            f"{cfg.name} models {d}; pick the matching PD config "
            "(einet_pd_mnist for mnist, einet_pd for svhn, einet_celeba "
            "for celeba)"
        )
    return data


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None,
                    help="registered EiNet config (required unless --smoke)")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny built-in arch, few steps, health telemetry "
                         "on (CI trace-smoke profile)")
    ap.add_argument("--steps", type=int, default=None)
    ap.add_argument("--batch", type=int, default=None,
                    help="global batch (default: the config's batch_size)")
    ap.add_argument("--model-parallel", type=int, default=1)
    ap.add_argument("--ckpt-dir", default="artifacts/ckpt")
    ap.add_argument("--checkpoint-every", type=int, default=25)
    ap.add_argument("--microbatches", type=int, default=1,
                    help="EiNet: scan-accumulate E-step statistics over this "
                         "many microbatches inside the compiled step")
    ap.add_argument("--em-mode", choices=("stochastic", "full"),
                    default="stochastic")
    ap.add_argument("--dataset",
                    choices=("synthetic", "mnist", "svhn", "celeba"),
                    default="synthetic",
                    help="EiNet training data (real datasets cache under "
                         "--data-dir; offline hosts fall back to the "
                         "procedural generator)")
    ap.add_argument("--data-dir", default=ds_lib.DEFAULT_DATA_DIR)
    ap.add_argument("--mixture", type=int, default=0,
                    help="EiNet: train a mixture of this many components "
                         "over k-means data clusters (§4.2 CelebA protocol) "
                         "with one vmapped lockstep EM update; 0 = single "
                         "model")
    ap.add_argument("--mixture-assign", choices=("hard", "soft"),
                    default="hard",
                    help="mixture E-step: hard per-cluster EM on stacked "
                         "batches, or soft responsibility-weighted EM on a "
                         "shared batch")
    ap.add_argument("--dist-em", action="store_true",
                    help="EiNet: use the shard_map psum-EM step over the "
                         "mesh's data axes (implied by multi-process runs)")
    ap.add_argument("--health", action="store_true",
                    help="force device-side health telemetry on (defaults "
                         "to the model knob / REPRO_HEALTH; implied by "
                         "--smoke; unsupported with --dist-em)")
    ap.add_argument("--on-divergence", choices=("abort", "continue"),
                    default="abort",
                    help="flight-recorder policy when the health vector "
                         "trips: dump an incident bundle then abort (raise) "
                         "or keep training")
    ap.add_argument("--trace", default=None, metavar="OUT.json",
                    help="collect obs tracing spans and export a "
                         "Chrome-trace JSON to this path at exit")
    ap.add_argument("--metrics", default=None, metavar="OUT.json",
                    help="write the METRICS.snapshot() JSON (including "
                         "train.health.* gauges) to this path at exit")
    args = ap.parse_args()
    if args.arch is None and not args.smoke:
        ap.error("--arch is required (or pass --smoke)")
    if args.steps is None:
        args.steps = 8 if args.smoke else 50
    obs.cli_begin(args.trace)

    if args.smoke:
        cfg = SMOKE_CONFIG
        args.arch = args.arch or cfg.name
    else:
        cfg = get_config(args.arch)
    global_batch = args.batch or cfg.batch_size
    mesh = make_mesh_for(model_parallel=args.model_parallel)
    rules = shlib.default_rules(multi_pod=False, fsdp=False)
    mgr = CheckpointManager(
        os.path.join(args.ckpt_dir, args.arch.replace("/", "_"))
    )

    with shlib.use_rules(rules), jax.set_mesh(mesh):
        if args.mixture >= 2:
            # §4.2 mixture-of-EiNets: k-means the data, stack C components,
            # advance them all with ONE vmapped jitted EM step.  (Mixture
            # training is single-process for now -- the stacked component
            # axis is not in the dist rule table yet.)
            if jax.process_count() > 1 or args.dist_em:
                raise SystemExit(
                    "--mixture does not compose with --dist-em / "
                    "multi-process yet; run single-process"
                )
            from repro import mixture as mx

            base = dr.build_einet(cfg)
            print(f"[plan] {args.arch}: "
                  f"{plan_lib.format_summary(base.grouping_summary())}")
            model = mx.EiNetMixture(base, args.mixture)
            data = einet_train_data(cfg, args.dataset, args.data_dir)
            mcfg = mx.MixtureTrainConfig(
                assign=args.mixture_assign, mode=args.em_mode,
                num_microbatches=args.microbatches, donate=False,
            )
            if args.mixture_assign == "hard":
                params, loader, km = mx.prepare_mixture_training(
                    model, data, seed=0, global_batch=global_batch,
                )
                print(f"[train] k-means clusters: {km.counts.tolist()} "
                      f"(inertia {km.inertia:.4f})")
            else:
                params = model.init(jax.random.PRNGKey(0))
                loader = einet_loader(data, global_batch)
            step_jit = mx.make_mixture_em_step(model, mcfg)

            def step_fn(state, batch):
                (p, _), ll = run_step(step_jit, state["params"], batch["x"])
                with obs.span("train.record"):
                    record_step(len(batch["x"]), ll)
                return {"params": p, "step": state["step"] + 1,
                        "last_ll": ll}

            init_state = {"params": params, "step": jnp.zeros((), jnp.int32),
                          "last_ll": 0.0}
        else:
            model = dr.build_einet(cfg)
            print(f"[plan] {args.arch}: "
                  f"{plan_lib.format_summary(model.grouping_summary())}")
            params = model.init(jax.random.PRNGKey(0))
            data = einet_train_data(cfg, args.dataset, args.data_dir)
            loader = einet_loader(
                data, global_batch,
                num_shards=jax.process_count(), shard_id=jax.process_index(),
            )
            # the whole EM update -- scan-accumulated E-step, M-step, blend --
            # is ONE compiled program.  donate=False: ft.run_training's
            # replay-from-init recovery path re-feeds the initial params when
            # a failure precedes the first committed checkpoint, so the step
            # must not consume them.
            # health telemetry: --smoke/--health force it on, otherwise the
            # model knob (REPRO_HEALTH) decides; the sharded psum-EM step
            # does not support the extra output, so --dist-em keeps it off
            dist = args.dist_em or jax.process_count() > 1
            health_knob = (
                False if dist
                else (True if (args.smoke or args.health) else None)
            )
            tcfg = TrainConfig(
                mode=args.em_mode, num_microbatches=args.microbatches,
                donate=False, health=health_knob)
            health_on = (
                model.health if tcfg.health is None else bool(tcfg.health)
            )
            watcher = None
            if health_on:
                watcher = health_lib.HealthWatcher(
                    model, health_lib.HealthPolicy(
                        on_incident=args.on_divergence)
                )
            if dist:
                # multi-process (or explicitly requested): disjoint
                # per-process shards REQUIRE the cross-shard statistics
                # psum inside the step -- the shard_map form makes it
                # explicit over the mesh's data axes.  (Closes the ROADMAP
                # "Distributed compiled EM" item; the loud guard PR 3 left
                # here is gone.)
                step_jit = make_sharded_em_step(model, tcfg, mesh)
            else:
                step_jit = make_em_step(model, tcfg)
            if jax.process_count() > 1:
                # each process's loader yields only its own disjoint rows;
                # the global-mesh step needs them assembled into one global
                # array sharded over the data axis (a host-local np array
                # is not addressable across processes)
                from jax.sharding import NamedSharding, PartitionSpec as P

                x_sh = NamedSharding(mesh, P("data"))

                def to_device(x):
                    return jax.make_array_from_process_local_data(
                        x_sh, np.asarray(x, np.float32)
                    )
            else:
                to_device = jnp.asarray

            def step_fn(state, batch):
                out, ll = run_step(step_jit, state["params"], batch["x"],
                                   to_device)
                with obs.span("train.record"):
                    record_step(len(batch["x"]), ll)
                    if watcher is not None:
                        health_lib.publish(model.health_spec, out[2])
                        watcher.observe(int(state["step"]), out[2], out[0])
                return {"params": out[0], "step": state["step"] + 1,
                        "last_ll": ll}

            init_state = {"params": params, "step": jnp.zeros((), jnp.int32),
                          "last_ll": 0.0}

        lls = []
        with obs.timed("train.run") as t_run:
            state, stats = ft.run_training(
                step_fn, init_state, loader.batch_at, mgr, args.steps,
                ft.LoopConfig(checkpoint_every=args.checkpoint_every),
                on_step=lambda s, st: lls.append(st["last_ll"]),
            )
    dt = t_run.seconds
    print(f"{args.arch}: {args.steps} steps, {dt/max(args.steps,1)*1e3:.0f} "
          f"ms/step, dp_shards={dp_shards(mesh)}, restarts={stats['restarts']}")
    print(f"objective: first {np.mean(lls[:5]):.3f} -> last {np.mean(lls[-5:]):.3f}")
    obs.cli_end(args.trace, args.metrics)


if __name__ == "__main__":
    from repro.launch import compile_cache

    compile_cache.enable()
    main()

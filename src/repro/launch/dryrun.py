import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# ^^ MUST precede any jax import: jax locks the device count on first init.
"""Multi-pod dry-run: lower + compile every EiNet architecture's EM-step cell
on the production meshes, and extract the roofline inputs.

For each cell this produces artifacts/dryrun/<arch>__em_step__<mesh>.json with:
  * cost_analysis flops / bytes accessed       (compute & memory terms)
  * memory_analysis argument/output/temp bytes (fits-in-HBM evidence)
  * per-collective byte counts parsed from the post-SPMD HLO
    (all-gather / all-reduce / reduce-scatter / all-to-all / collective-permute)
  * lowering/compile wall times

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch einet_rat --mesh single
  PYTHONPATH=src python -m repro.launch.dryrun --all --mesh both
"""

import argparse
import json
import traceback
from typing import Any, Dict, Optional

import jax
import numpy as np

from repro import obs
from repro.analysis.verify import VerifyError, verify_config, verify_einet
from repro.configs import REGISTRY, get_config
from repro.core import plan as plan_lib
from repro.launch.hlo_analysis import analyze_hlo
from repro.launch.mesh import make_production_mesh

from repro.launch.cells import lower_einet_cell  # noqa: E402


def run_cell(arch: str, mesh_kind: str, out_dir: str,
             skip_existing: bool = True) -> Optional[Dict[str, Any]]:
    cfg = get_config(arch)
    multi_pod = mesh_kind == "multi"
    tag = f"{arch}__em_step__{'2x16x16' if multi_pod else '16x16'}"
    path = os.path.join(out_dir, tag.replace("/", "_") + ".json")
    if skip_existing and os.path.exists(path):
        print(f"[skip-cached] {tag}")
        with open(path) as f:
            return json.load(f)

    mesh = make_production_mesh(multi_pod=multi_pod)
    print(f"[lower] {tag} ...", flush=True)
    try:
        with jax.set_mesh(mesh):
            lowered, t_lower, model = lower_einet_cell(cfg, mesh, multi_pod)
            print(f"[plan] {arch}: "
                  f"{plan_lib.format_summary(model.grouping_summary())}",
                  flush=True)
            report = verify_einet(model, name=arch)
            print(f"[verify] {arch}: {report.summary()}", flush=True)
            if not report.ok:
                raise VerifyError(report)
            with obs.timed("compile.cell", arch=arch) as t:
                compiled = lowered.compile()
            t_compile = t.seconds
        cost = compiled.cost_analysis()
        ma = compiled.memory_analysis()
        hlo = compiled.as_text()
        # scan-aware per-device totals (XLA cost_analysis counts while bodies
        # once; analyze_hlo multiplies by known_trip_count -- see hlo_analysis)
        corr = analyze_hlo(hlo)
        rec = {
            "arch": arch,
            "shape": "em_step",
            "mesh": "2x16x16" if multi_pod else "16x16",
            "num_devices": int(np.prod(list(mesh.shape.values()))),
            "kind": "train",
            # raw XLA aggregate (loop bodies counted once) -- kept for reference
            "xla_flops_raw": float(cost.get("flops", -1)),
            "xla_bytes_raw": float(cost.get("bytes accessed", -1)),
            # corrected per-device totals
            "flops_per_device": corr["flops"],
            "bytes_written_per_device": corr["bytes_written"],
            "collectives": corr["collectives"],
            "collective_bytes_per_device": corr["collective_bytes"],
            "memory": {
                "argument_bytes": ma.argument_size_in_bytes,
                "output_bytes": ma.output_size_in_bytes,
                "temp_bytes": ma.temp_size_in_bytes,
                "alias_bytes": ma.alias_size_in_bytes,
            },
            "lower_s": round(t_lower, 2),
            "compile_s": round(t_compile, 2),
            "param_count": None,
            "active_param_count": None,
            "grouping": model.grouping_summary(),
            "hlo_bytes": len(hlo),
        }
        os.makedirs(out_dir, exist_ok=True)
        with open(path, "w") as f:
            json.dump(rec, f, indent=1)
        print(f"[ok] {tag}: {rec['flops_per_device']:.3e} flops/dev, "
              f"{rec['collective_bytes_per_device']:.3e} coll B/dev, "
              f"compile {t_compile:.1f}s", flush=True)
        return rec
    except Exception as e:  # noqa: BLE001 -- a failed cell is a bug; record it
        rec = {"arch": arch, "shape": "em_step", "mesh": mesh_kind,
               "error": repr(e), "traceback": traceback.format_exc()}
        os.makedirs(out_dir, exist_ok=True)
        with open(path + ".err", "w") as f:
            json.dump(rec, f, indent=1)
        print(f"[FAIL] {tag}: {e}", flush=True)
        return rec


def run_verify(archs) -> int:
    """Static circuit/plan verification per arch (no lowering, no mesh):
    the ``--verify`` CI gate.  Returns the number of failing archs."""
    failures = 0
    for arch in archs:
        report = verify_config(get_config(arch))
        print(f"[verify] {arch}: {report.summary()}", flush=True)
        for finding in report.findings:
            print(f"  - {finding}", flush=True)
        failures += 0 if report.ok else 1
    return failures


# archs whose parameter pytree exceeds this many floats skip the numerical
# probe (an eager forward on einet_rat_large's 530M params is a dry-run
# budget, not a smoke test)
PROBE_PARAM_FLOOR = 80_000_000
PROBE_BATCH = 8


def _probe_data(model, batch: int) -> np.ndarray:
    """A batch in the arch's EF data domain (lgamma/one-hot blow up on
    out-of-domain floats, which would make the probe report false alarms)."""
    rng = np.random.RandomState(0)
    name = model.ef.name
    if name == "binomial":
        hi = model.ef.n_trials
        return rng.randint(0, hi + 1, (batch, model.num_vars)).astype(
            np.float32)
    if name == "categorical":
        hi = model.ef.num_categories
        return rng.randint(0, hi, (batch, model.num_vars)).astype(np.float32)
    if name == "bernoulli":
        return rng.randint(0, 2, (batch, model.num_vars)).astype(np.float32)
    return rng.randn(batch, model.num_vars).astype(np.float32)


def run_health_probe(archs, out_dir: str = "artifacts/health") -> int:
    """Numerical-health probe per arch: one eager forward at init params
    through the tap sites (``repro.obs.health``), recording per-segment
    saturation and batch-LL health to ``artifacts/health/<arch>.json``.

    Catches init-time numerical rot (a config whose leaves saturate on
    in-domain data before training even starts) that static verification
    can't see.  Probe *errors* warn and are recorded but do not fail the
    gate -- only a non-finite LL on in-domain data counts as a failure.
    Returns the number of failing archs.
    """
    import jax.numpy as jnp

    from repro.launch.cells import build_einet
    from repro.obs import health as health_lib

    failures = 0
    os.makedirs(out_dir, exist_ok=True)
    for arch in archs:
        path = os.path.join(out_dir, arch.replace("/", "_") + ".json")
        try:
            model = build_einet(get_config(arch))
            shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
            n_params = sum(
                int(np.prod(s.shape))
                for s in jax.tree_util.tree_leaves(shapes)
            )
            if n_params > PROBE_PARAM_FLOOR:
                rec = {"arch": arch, "skipped": True,
                       "num_params": n_params,
                       "reason": f"param count {n_params} > probe floor "
                                 f"{PROBE_PARAM_FLOOR}"}
                print(f"[health] {arch}: skipped ({n_params/1e6:.0f}M "
                      "params)", flush=True)
            else:
                params = model.init(jax.random.PRNGKey(0))
                x = jnp.asarray(_probe_data(model, PROBE_BATCH))
                leaf_rows = model.leaf_rows(params, x)
                with health_lib.collect() as taps:
                    root = model.forward_from_leaves(
                        params["einsum"], params["mixing"], leaf_rows
                    )
                ll = jax.scipy.special.logsumexp(
                    root + jnp.log(params["class_prior"])[None, :], axis=-1
                )
                ll = np.asarray(ll)
                rec = {
                    "arch": arch,
                    "skipped": False,
                    "num_params": n_params,
                    "probe_batch": PROBE_BATCH,
                    "ll_mean": float(np.mean(ll)),
                    "ll_min": float(np.min(ll)),
                    "ll_nonfinite": int(np.sum(~np.isfinite(ll))),
                    "leaf_sat_frac": float(
                        health_lib.saturation_fraction(leaf_rows)),
                    "segment_sat_frac": [float(t) for t in taps],
                }
                ok = rec["ll_nonfinite"] == 0
                failures += 0 if ok else 1
                print(f"[health] {arch}: ll mean {rec['ll_mean']:.2f} "
                      f"min {rec['ll_min']:.2f}, leaf sat "
                      f"{rec['leaf_sat_frac']:.3f}, "
                      f"{len(taps)} segment(s)"
                      + ("" if ok else "  <-- NON-FINITE"), flush=True)
        except Exception as e:  # noqa: BLE001 -- probe breakage must not
            # mask the verify gate; record and move on
            rec = {"arch": arch, "skipped": True, "reason": repr(e)}
            print(f"[health] {arch}: probe error (not fatal): {e}",
                  flush=True)
        with open(path, "w") as f:
            json.dump(rec, f, indent=1)
    return failures


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--mesh", choices=("single", "multi", "both"), default="single")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="artifacts/dryrun")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--verify", action="store_true",
                    help="run the static circuit/plan verifier over the "
                         "selected archs and exit (non-zero on any failed "
                         "invariant); no lowering or compilation")
    ap.add_argument("--trace", default=None, metavar="OUT.json",
                    help="collect obs tracing spans and export a "
                         "Chrome-trace JSON to this path at exit")
    args = ap.parse_args()
    obs.cli_begin(args.trace)

    meshes = {"single": ["single"], "multi": ["multi"],
              "both": ["single", "multi"]}[args.mesh]
    if args.all or args.arch is None:
        archs = sorted(REGISTRY)
    else:
        archs = [args.arch]

    if args.verify:
        failures = run_verify(archs)
        failures += run_health_probe(archs)
        if failures:
            raise SystemExit(f"{failures} arch(s) failed verification")
        print(f"verification complete: {len(archs)} arch(s) clean")
        obs.cli_end(args.trace)
        return

    failures = 0
    for mesh_kind in meshes:
        for arch in archs:
            rec = run_cell(arch, mesh_kind, args.out,
                           skip_existing=not args.force)
            if rec and "error" in rec:
                failures += 1
    if failures:
        raise SystemExit(f"{failures} cells failed")
    print("dry-run complete")
    obs.cli_end(args.trace)


if __name__ == "__main__":
    from repro.launch import compile_cache

    compile_cache.enable()
    main()

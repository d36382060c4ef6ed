"""Assemble EXPERIMENTS.md from the dry-run artifacts + benchmark JSONs +
the hand-written §Perf iteration log (kept in benchmarks/perf_log.md).

Degrades gracefully: sections whose artifacts have not been generated on
this host (the dry-run sweep needs the 512-device subprocess run) render a
placeholder instead of crashing, so the §Perf log that module docstrings
cite is always available.

PYTHONPATH=src:. python -m benchmarks.make_experiments_md
"""

import json
import os

from benchmarks import roofline

_MISSING = ("_not yet generated on this host — run "
            "`python -m repro.launch.dryrun` first._")


def dryrun_summary(art_dir: str, mesh: str) -> str:
    if not os.path.isdir(art_dir):
        return _MISSING
    rows = []
    ok = skip = 0
    for f in sorted(os.listdir(art_dir)):
        if not f.endswith(".json"):
            continue
        rec = json.load(open(os.path.join(art_dir, f)))
        if rec.get("mesh") not in (mesh, None) and "skipped" not in rec:
            continue
        if "skipped" in rec:
            skip += 1
            continue
        if "error" in rec:
            rows.append(f"| {rec['arch']} | {rec.get('shape')} | ERROR |")
            continue
        ok += 1
        mem = rec["memory"]
        rows.append(
            f"| {rec['arch']} | {rec['shape']} | {rec['kind']} | "
            f"{rec['flops_per_device']:.2e} | "
            f"{rec['collective_bytes_per_device']:.2e} | "
            f"{(mem['argument_bytes'])/1e9:.1f} | "
            f"{(mem['temp_bytes'])/1e9:.1f} | {rec['compile_s']:.0f} |"
        )
    hdr = ("| arch | shape | kind | FLOPs/dev | coll B/dev | args GB | "
           "temp GB | compile s |\n|" + "---|" * 8)
    return (f"{ok} cells compiled, {skip} documented skips.\n\n" + hdr + "\n"
            + "\n".join(rows))


def roofline_summary(art_dir: str, mesh: str) -> str:
    if not os.path.isdir(art_dir):
        return _MISSING
    return roofline.to_markdown(roofline.build_table(art_dir, mesh))


def bench_summary() -> str:
    """One row per benchmark JSON snapshot present at the repo root."""
    parts = []
    if os.path.isfile("BENCH_serve.json"):
        r = json.load(open("BENCH_serve.json"))
        pc = r.get("program_cache") or {}
        cache_s = (f" Program cache: {pc.get('hits', 0)} hits / "
                   f"{pc.get('misses', 0)} misses "
                   f"({pc.get('registry_compiles', 0)} registry compiles)."
                   if pc else "")
        parts.append(
            f"**Serving** (`BENCH_serve.json`, {r.get('arch')}): engine "
            f"{r.get('engine_qps', 0):.1f} req/s — "
            f"x{r.get('speedup', 0):.1f} vs the pre-engine per-request path, "
            f"x{r.get('speedup_vs_jitted', 0):.1f} vs a fully-jitted "
            f"per-request baseline; parity {r.get('parity_max_abs_diff')}."
            + cache_s
        )
        lat = r.get("latency_ms") or {}
        if lat:
            rows = ["| kind | p50 ms | p95 ms | p99 ms |",
                    "|" + "---|" * 4]
            for kind, lm in sorted(lat.items()):
                rows.append(
                    f"| {kind} | {lm.get('p50', 0):.3f} | "
                    f"{lm.get('p95', 0):.3f} | {lm.get('p99', 0):.3f} |"
                )
            parts.append(
                "Steady-state per-request latency (enqueue → complete, "
                "from the engine's `serve.request.seconds` histograms; "
                "warm-up excluded):\n\n" + "\n".join(rows)
            )
    if os.path.isfile("BENCH_eval.json"):
        r = json.load(open("BENCH_eval.json"))
        parity = ("0 mismatches" if r.get("parity_ok")
                  else f"{r.get('parity_mismatches')} MISMATCHES")
        parts.append(
            f"**Evaluation** (`BENCH_eval.json`, {r.get('arch')}): held-out "
            f"LL through the serving engine at "
            f"{r.get('engine_rows_per_s', 0):.0f} rows/s vs "
            f"{r.get('direct_rows_per_s', 0):.0f} rows/s for the dense "
            f"engine-free loop (x{r.get('engine_vs_direct', 0):.2f}); "
            f"inpainting {r.get('inpaint_requests_per_s', 0):.0f} req/s; "
            f"engine-vs-direct parity {parity}."
        )
    if os.path.isfile("BENCH_train.json"):
        r = json.load(open("BENCH_train.json"))
        rows = ["| arch | batch (microbatches) | compiled ms/step | "
                "per-step ms/step | speedup | launches | segment split "
                "(eager) | grad parity |",
                "|" + "---|" * 8]
        for c in r.get("results", []):
            g = c.get("grouping") or {}
            launches = (f"{g['launches_per_layer']} -> {g['launches_grouped']}"
                        if g else "—")
            seg = c.get("segment_breakdown") or {}
            seg_s = ", ".join(
                f"{k}: {v['launches']}× {v['eager_ms']:.1f} ms"
                for k, v in sorted(seg.items())
            ) or "—"
            rows.append(
                f"| {c['arch']} | {c['batch']} ({c['microbatches']}) | "
                f"{c['fused_ms_per_step']} | {c['per_step_ms_per_step']} | "
                f"x{c['speedup']} | {launches} | {seg_s} | "
                f"{c['grad_parity_max_abs_diff']:.1e} |"
            )
        parts.append(
            "**Training** (`BENCH_train.json`, backend "
            f"{r.get('backend')}): compiled EM step vs the seed's per-step "
            "path; the segment split is one eager forward per arch timed "
            "through the obs `plan.segment` spans (relative per-kind cost, "
            "not compiled absolute time).\n\n" + "\n".join(rows)
        )
        sc = r.get("leaf_scatter")
        if sc:
            parts.append(
                f"**Leaf EM fan-out** (`BENCH_train.json`, {sc.get('arch')} "
                f"at batch {sc.get('batch')}): the leaf statistics "
                f"(`core.em.leaf_statistics`, fan-out to (D, K, R, |T|) "
                f"included) cost "
                f"{sc.get('leaf_scatter_ms')} ms of the "
                f"{sc.get('em_statistics_ms')} ms `em_statistics` call "
                f"({100 * sc.get('scatter_fraction', 0):.1f}%) — the ROADMAP "
                "\"fuse or not\" answer: not worth a fused kernel at this "
                "scale."
            )
    if os.path.isfile("BENCH_mixture.json"):
        r = json.load(open("BENCH_mixture.json"))
        cells = r.get("results") or []
        rows = ["| cell | C | batch/component | vmapped ms/step | "
                "looped ms/step | speedup | param parity |",
                "|" + "---|" * 7]
        for c in cells:
            rows.append(
                f"| {c['cell']} | {c['num_components']} | "
                f"{c['per_component_batch']} | {c['vmapped_ms_per_step']} | "
                f"{c['looped_ms_per_step']} | x{c['speedup']} | "
                f"{c['param_parity_max_abs_diff']:.1e} |"
            )
        comp_arch = cells[0].get("component_arch") if cells else "?"
        parts.append(
            "**Mixture training** (`BENCH_mixture.json`, backend "
            f"{r.get('backend')}, component {comp_arch}"
            "): ONE vmapped C-component EM step vs a Python loop of C "
            "single-model steps (identical update; parity is bitwise).\n\n"
            + "\n".join(rows)
        )
    return "\n\n".join(parts) if parts else _MISSING


def _eval_records(root: str):
    """Per-run metrics JSONs under ``root``.  Deliberately NOT imported from
    repro.eval.grids: that would pull jax + the serve/train stack into this
    dependency-light generator, breaking its degrade-gracefully contract on
    hosts without them."""
    records = []
    if not os.path.isdir(root):
        return records
    for run in sorted(os.listdir(root)):
        p = os.path.join(root, run, "metrics.json")
        if os.path.isfile(p):
            records.append(json.load(open(p)))
    return records


def eval_summary(root: str = "artifacts/eval") -> str:
    """The Fig. 4 section: one block per eval-workbench run
    (``repro.launch.eval`` writes ``artifacts/eval/<run>/metrics.json``)."""
    records = _eval_records(root)
    if not records:
        return ("_no eval runs on this host — run "
                "`PYTHONPATH=src python -m repro.launch.eval "
                "--dataset synthetic --smoke` first._")
    parts = []
    for r in records:
        bj = r.get("bpd_joint", {})
        bm = r.get("bpd_marginal", {})
        rows = ["| mask | sample MSE | MPE MSE | mean-fill MSE |",
                "|" + "---|" * 4]
        for mk, m in r.get("inpainting", {}).get("per_mask", {}).items():
            mf = m.get("mean_fill_mse")
            rows.append(
                f"| {mk} | {m.get('conditional_sample_mse', 0):.4f} | "
                f"{m.get('mpe_mse', 0):.4f} | "
                f"{'—' if mf is None else f'{mf:.4f}'} |"
            )
        mix_s = ""
        if r.get("mixture_components"):
            mix_s = (f", mixture of {r['mixture_components']} EiNets over "
                     f"k-means clusters {r.get('cluster_sizes')}")
        parts.append(
            f"**{r.get('run_name')}** — {r.get('dataset')} "
            f"({r.get('dataset_source')}), "
            f"{r.get('height')}x{r.get('width')}x{r.get('channels')}, "
            f"{r.get('num_params', 0):,} params, {r.get('train_steps')} EM "
            f"steps{mix_s}; test bpd {bj.get('bpd', 0):.4f} "
            f"({bj.get('num_rows')} rows at "
            f"{bj.get('engine_rows_per_s', 0):.0f} rows/s through the "
            f"engine), marginal bpd ({bm.get('mask')}) "
            f"{bm.get('bpd', 0):.4f}; engine-vs-direct parity mismatches "
            f"{r.get('parity_mismatches_total')}.\n\n" + "\n".join(rows)
        )
    return "\n\n".join(parts)


def health_summary(root: str = "artifacts/health") -> str:
    """One row per arch from the dry-run numerical-health probe
    (``repro.launch.dryrun --verify`` writes ``artifacts/health/*.json``)."""
    if not os.path.isdir(root):
        return ("_no health probe records on this host — run "
                "`PYTHONPATH=src python -m repro.launch.dryrun --verify` "
                "first._")
    rows = ["| arch | params | probe LL mean | LL min | non-finite | "
            "leaf sat | segment sat (max) |",
            "|" + "---|" * 7]
    for f in sorted(os.listdir(root)):
        if not f.endswith(".json"):
            continue
        rec = json.load(open(os.path.join(root, f)))
        if rec.get("skipped"):
            reason = rec.get("reason", "?")
            rows.append(f"| {rec.get('arch')} | — | — | — | — | — | "
                        f"skipped: {reason} |")
            continue
        seg = rec.get("segment_sat_frac") or [0.0]
        rows.append(
            f"| {rec['arch']} | {rec.get('num_params', 0):,} | "
            f"{rec['ll_mean']:.2f} | {rec['ll_min']:.2f} | "
            f"{rec['ll_nonfinite']} | {rec['leaf_sat_frac']:.3f} | "
            f"{max(seg):.3f} over {len(seg)} segment(s) |"
        )
    return "\n".join(rows)


def bench_history_summary(root: str = "artifacts/bench_history",
                          last: int = 5) -> str:
    """Recent commit-stamped rows per bench kind from the JSONL history
    (``repro.obs.slo.append_history``; read directly so this generator
    stays import-free)."""
    if not os.path.isdir(root):
        return ("_no bench history on this host — any "
                "`python -m benchmarks.bench_*` run appends to it._")
    parts = []
    for fname in sorted(os.listdir(root)):
        if not fname.endswith(".jsonl"):
            continue
        rows = []
        with open(os.path.join(root, fname)) as f:
            for line in f:
                line = line.strip()
                if not line:
                    continue
                try:
                    rows.append(json.loads(line))
                except json.JSONDecodeError:
                    continue
        if not rows:
            continue
        kind = fname[: -len(".jsonl")]
        md = [f"**{kind}** ({len(rows)} run(s) recorded):", "",
              "| commit | when (UTC) | profile | headline |",
              "|" + "---|" * 4]
        for r in rows[-last:]:
            if kind == "serve":
                head = (f"x{r.get('speedup_vs_jitted', 0):.2f} vs jitted, "
                        f"{r.get('engine_qps', 0):.0f} req/s")
            elif kind == "train":
                cells = r.get("cells") or {}
                head = ", ".join(
                    f"{a}: {c.get('fused_ms') or 0:.1f} ms"
                    for a, c in sorted(cells.items())) or "—"
            elif kind == "mixture":
                cells = r.get("cells") or {}
                head = ", ".join(f"{c}: x{s:.2f}"
                                 for c, s in sorted(cells.items())) or "—"
            else:
                head = f"engine/direct x{r.get('engine_vs_direct') or 0:.2f}"
            md.append(
                f"| {r.get('commit', '?')} | "
                f"{str(r.get('ts', '?'))[:16]} | "
                f"{'smoke' if r.get('smoke') else 'full'} | {head} |")
        parts.append("\n".join(md))
    return "\n\n".join(parts) if parts else (
        "_no bench history on this host — any "
        "`python -m benchmarks.bench_*` run appends to it._")


def verify_summary() -> str:
    """Verifier-coverage row per registered arch.  Needs jax (the circuit
    is built to be verified); degrades to a placeholder without it."""
    try:
        from repro.analysis.verify import verify_config
        from repro.configs import REGISTRY as configs
    except Exception:  # noqa: BLE001 -- dependency-light contract
        return ("_verifier unavailable on this host (requires jax) — run "
                "`PYTHONPATH=src python -m repro.launch.dryrun --verify`._")
    rows = ["| arch | pairs | plan | invariants checked | findings | status |",
            "|" + "---|" * 6]
    for name in sorted(configs):
        try:
            from repro.launch.cells import build_einet

            model = build_einet(configs[name])
            report = verify_config(configs[name])
            s = model.plan.summary()
            plan = (f"{s['fused_groups']} fused + {s['gather_groups']} "
                    f"gather / {s['num_pairs']} pairs")
            rows.append(
                f"| {report.name} | {len(model.pair_specs)} | {plan} | "
                f"{len(report.invariants)} | {len(report.findings)} | "
                f"{'ok' if report.ok else 'FAILED'} |")
        except Exception as e:  # noqa: BLE001 -- a failed build is a row
            rows.append(f"| {name} | — | — | — | — | ERROR: {e!r} |")
    return "\n".join(rows)


def main():
    base = roofline_summary("artifacts/dryrun_baseline", "16x16")
    opt_dir = "artifacts/dryrun_opt" if os.path.isdir("artifacts/dryrun_opt") \
        else "artifacts/dryrun"
    opt = roofline_summary(opt_dir, "16x16")
    single = dryrun_summary(opt_dir, "16x16")
    multi = dryrun_summary("artifacts/dryrun", "2x16x16")
    perf = open("benchmarks/perf_log.md").read()
    header = open("benchmarks/experiments_header.md").read()
    out = header
    out = out.replace("{{VERIFY}}", verify_summary())
    out = out.replace("{{DRYRUN_SINGLE}}", single)
    out = out.replace("{{DRYRUN_MULTI}}", multi)
    out = out.replace("{{ROOFLINE_BASELINE}}", base)
    out = out.replace("{{ROOFLINE_OPT}}", opt)
    out = out.replace("{{HEALTH}}", health_summary())
    out = out.replace("{{BENCHES}}", bench_summary())
    out = out.replace("{{BENCH_HISTORY}}", bench_history_summary())
    out = out.replace("{{EVAL}}", eval_summary())
    out = out.replace("{{PERF_LOG}}", perf)
    with open("EXPERIMENTS.md", "w") as f:
        f.write(out)
    print("wrote EXPERIMENTS.md", len(out), "bytes")


if __name__ == "__main__":
    main()

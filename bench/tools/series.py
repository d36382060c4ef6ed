"""Run benchmark runs one after another, each in its own process, and keep
each run's result line and its ``[bench]``/``[check]`` lines.

    python3 bench/tools/series.py OUT.jsonl "CELL SEED SECONDS TRACE [PLANT [SET]]" ...

A fifth field other than ``-`` runs ``bench/tools/plant.py`` with that plant
instead of ``bench/run.py``; a sixth names the set the run belongs to (read
by ``spread.py``).  This process never imports JAX, so each run owns the
chip.
"""
import json
import subprocess
import sys
import time


def main(out, specs):
    with open(out, "a") as f:
        for spec in specs:
            parts = spec.split()
            cell, seed, seconds, trace = parts[:4]
            cmd = ["python3", "bench/run.py"]
            if len(parts) > 4 and parts[4] != "-":
                cmd = ["python3", "bench/tools/plant.py", parts[4]]
            cmd += ["--workload", cell, "--seed", seed, "--seconds", seconds, "--trace", trace]
            t0 = time.time()
            p = subprocess.run(cmd, capture_output=True, text=True)
            lines = p.stdout.strip().splitlines()
            rec = {"spec": spec, "rc": p.returncode, "wall_s": time.time() - t0,
                   "result": json.loads(lines[-1]) if p.returncode == 0 and lines else None,
                   "log": [l for l in p.stderr.splitlines() if l.startswith(("[bench]", "[check]"))],
                   "err_tail": p.stderr[-3000:] if p.returncode else ""}
            f.write(json.dumps(rec) + "\n")
            f.flush()
            r = rec["result"] or {}
            print(spec, "rc", p.returncode, "wall", round(rec["wall_s"], 1),
                  "correct", r.get("correct"), json.dumps(r.get("metrics")),
                  json.dumps({k: v["value"] for k, v in r.get("checks", {}).items()}),
                  "mem", (r.get("device") or {}).get("memory_peak_bytes"), flush=True)
            for l in rec["log"]:
                print("   ", l[:400], flush=True)
            if p.returncode:
                print(rec["err_tail"][-1500:], flush=True)


if __name__ == "__main__":
    main(sys.argv[1], sys.argv[2:])

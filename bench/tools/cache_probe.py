"""Check that a second process finds a cell's programs in the persistent
compilation cache, and name the part of JAX's cache key that differs where
it does not.

    python3 bench/tools/cache_probe.py --workload pd_svhn.em_b512 --seed 5 --seconds 1

Runs the cell twice, each run in its own process with JAX's cache-key
logging on, and prints per program the key parts that differ between the
two runs.  This process never imports JAX, so each run owns the chip.
"""
import collections
import logging
import os
import re
import subprocess
import sys
import time

_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
PART = re.compile(r"get_cache_key hash of serialized (.+?): ([0-9a-f]+)")
KEY = re.compile(r"cache (?:hit|miss) for '([^']+)' with key '([^']+)'", re.I)


def child(argv):
    t_process = time.perf_counter()
    sys.path[:0] = [os.path.join(_ROOT, "src"), os.path.join(_ROOT, "bench")]
    import jax

    logging.basicConfig(level=logging.WARNING, stream=sys.stderr,
                        format="%(name)s %(levelname)s %(message)s")
    logging.getLogger("jax._src.cache_key").setLevel(logging.DEBUG)
    logging.getLogger("jax._src.compiler").setLevel(logging.DEBUG)
    jax.config.update("jax_explain_cache_misses", True)
    from harness.main import main

    return main(t_process, argv)


def programs(log):
    """[(module key, {part: hash})] in the order the run compiled them."""
    out, parts = [], collections.OrderedDict()
    for line in log.splitlines():
        m = PART.search(line)
        if m:
            parts[m.group(1)] = m.group(2)
            continue
        m = KEY.search(line)
        if m and parts:
            out.append((m.group(2), dict(parts)))
            parts = collections.OrderedDict()
    return out


def parent(argv):
    logs = []
    for i in range(2):
        t0 = time.time()
        p = subprocess.run([sys.executable, __file__, "--child"] + argv,
                           capture_output=True, text=True, cwd=_ROOT)
        logs.append(p.stderr)
        hits = len(re.findall(r"persistent compilation cache hit", p.stderr, re.I))
        setup = re.findall(r"\[bench\] set-up.*", p.stderr)
        print(f"run {i}: rc {p.returncode}, {time.time() - t0:.1f} s, logged hits {hits}",
              *setup, sep="\n  ", flush=True)
        os.makedirs(os.path.join(_ROOT, "chiprun_out"), exist_ok=True)
        with open(os.path.join(_ROOT, "chiprun_out", f"cache_probe_{i}.log"), "w") as f:
            f.write(p.stderr)
    first, second = programs(logs[0]), programs(logs[1])
    print(f"programs keyed: {len(first)} then {len(second)}")
    for (k1, p1), (k2, p2) in zip(first, second):
        diff = [n for n in p1 if p1[n] != p2.get(n)]
        print(("same " if k1 == k2 else "DIFF ") + k1.split("-")[0], diff)


if __name__ == "__main__":
    if sys.argv[1:2] == ["--child"]:
        sys.exit(child(sys.argv[2:]))
    parent(sys.argv[1:])

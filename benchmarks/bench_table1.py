"""Table 1: EiNet vs the naive (LibSPN/SPFlow-style) implementation.

The paper's Table 1 shows EiNets reproduce RAT-SPN test log-likelihoods on
the 20 binary datasets.  The datasets are not downloadable here (DESIGN.md
§6), so this benchmark checks the *implementation claim* on identically-sized
synthetic proxies:

  1. LL parity: the einsum layers and the naive log-sum-exp layers compute the
     same circuit -- max |dLL| must be float-level on every dataset;
  2. EM trains: test LL after 10 EM epochs beats the epoch-0 model on every
     dataset.

CSV: name,num_vars,ll_einsum,ll_naive,max_abs_diff,ll_after_em
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import (
    Bernoulli,
    EiNet,
    NaiveEiNet,
    em_update,
    random_binary_trees,
)
from repro.data.synthetic import TWENTY_DATASETS, binary_dataset

# keep CPU runtime bounded: every dataset, subsampled var-count cap
MAX_VARS = 200
N_TRAIN, N_TEST = 400, 200


def run(quick: bool = False):
    rows = []
    datasets = TWENTY_DATASETS[:6] if quick else TWENTY_DATASETS
    for name, dims in datasets:
        d = min(dims, MAX_VARS)
        data = binary_dataset(name, N_TRAIN + N_TEST)[:, :d]
        train = jnp.asarray(data[:N_TRAIN])
        test = jnp.asarray(data[N_TRAIN:])
        depth = min(3, int(np.log2(d)))
        g = random_binary_trees(d, depth, 4, seed=0)
        net = EiNet(g, num_sums=8, exponential_family=Bernoulli())
        naive = NaiveEiNet(g, num_sums=8, exponential_family=Bernoulli())
        params = net.init(jax.random.PRNGKey(0))
        ll_e = np.asarray(net.log_likelihood(params, test))
        ll_n = np.asarray(naive.log_likelihood(params, test))
        diff = float(np.max(np.abs(ll_e - ll_n)))
        p = params
        for _ in range(3 if quick else 10):
            p, _ = em_update(net, p, train)
        ll_after = float(np.mean(np.asarray(net.log_likelihood(p, test))))
        rows.append((name, d, float(ll_e.mean()), float(ll_n.mean()), diff,
                     ll_after))
    return rows


def main(quick: bool = False):
    t0 = time.time()
    rows = run(quick)
    print("name,num_vars,ll_einsum,ll_naive,max_abs_diff,ll_after_em")
    ok = True
    for r in rows:
        print(",".join(str(x) for x in r))
        ok &= r[4] < 1e-3 and r[5] > r[2]
    print(f"# parity+improvement on all datasets: {ok}; {time.time()-t0:.1f}s")
    return ok


if __name__ == "__main__":
    from repro.launch import compile_cache

    compile_cache.enable()
    main()

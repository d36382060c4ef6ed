"""Fig. 4: EiNets as generative image models + tractable inpainting.

SVHN/CelebA are not downloadable (DESIGN.md §6); a structured Gaussian-mixture
image proxy of the same shape (32x32 RGB by default) stands in.  The protocol
follows §4.2: PD structure with vertical splits (Delta splits), factorized
Gaussian leaves over channels, stochastic EM (lambda=0.5), variance projected
to [1e-6, 1e-2] via the EF's project_phi.

Outputs (artifacts/fig4/):
  samples.npy        -- unconditional samples
  inpainted.npy      -- left-half evidence, right half sampled from p(.|e)
  originals.npy
CSV to stdout: metric,value -- train LL trajectory + inpainting MSE vs a
mean-imputation baseline (the tractability payoff must beat it).
"""

from __future__ import annotations

import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import EiNet, Normal, poon_domingos
from repro.core.em import EMConfig, stochastic_em_update
from repro.data.synthetic import gaussian_mixture_images


def run(quick: bool = False, out_dir: str = "artifacts/fig4"):
    h = w = 12 if quick else 24
    c = 3
    n_train = 600 if quick else 3000
    epochs = 3 if quick else 8
    data = gaussian_mixture_images(n_train + 64, h, w, c, seed=0)
    train, test = data[:n_train], data[n_train:]
    g = poon_domingos(h, w, delta=max(2, h // 4), num_channels=c, axes=("w",))
    net = EiNet(g, num_sums=8 if quick else 16,
                exponential_family=Normal(min_var=1e-6, max_var=1e-2))
    params = net.init(jax.random.PRNGKey(0))
    step = jax.jit(lambda p, b: stochastic_em_update(
        net, p, b, EMConfig(step_size=0.5)))
    bs = 128
    lls = []
    t0 = time.time()
    for ep in range(epochs):
        perm = np.random.RandomState(ep).permutation(n_train)
        for i in range(0, n_train - bs + 1, bs):
            batch = jnp.asarray(train[perm[i: i + bs]])
            params, ll = step(params, batch)
        lls.append(float(ll))
    train_time = time.time() - t0

    # unconditional samples
    samples = np.asarray(net.sample(params, jax.random.PRNGKey(1), 16))
    # inpainting: observe the left half, sample the right half
    xt = jnp.asarray(test[:16])
    mask = np.zeros((16, h, w, c), bool)
    mask[:, :, : w // 2, :] = True
    mask = jnp.asarray(mask.reshape(16, -1))
    inpainted = np.asarray(
        net.conditional_sample(params, jax.random.PRNGKey(2), xt, mask)
    )
    # MSE metric uses the MPE-style argmax decode (a sample adds the model's
    # own output variance, which is not an error of the conditional)
    recon = np.asarray(
        net.conditional_sample(params, jax.random.PRNGKey(3), xt, mask,
                               mode="argmax")
    )
    # baseline: fill missing with the training mean
    mean_fill = np.where(np.asarray(mask), np.asarray(xt),
                         train.mean(0, keepdims=True))
    m = ~np.asarray(mask)
    mse_einet = float(np.mean((recon - np.asarray(xt))[m] ** 2))
    mse_mean = float(np.mean((mean_fill - np.asarray(xt))[m] ** 2))

    os.makedirs(out_dir, exist_ok=True)
    np.save(os.path.join(out_dir, "samples.npy"), samples.reshape(16, h, w, c))
    np.save(os.path.join(out_dir, "inpainted.npy"),
            inpainted.reshape(16, h, w, c))
    np.save(os.path.join(out_dir, "originals.npy"),
            np.asarray(xt).reshape(16, h, w, c))
    return {
        "ll_first_epoch": lls[0],
        "ll_last_epoch": lls[-1],
        "train_s": train_time,
        "inpaint_mse": mse_einet,
        "meanfill_mse": mse_mean,
        "samples_finite": bool(np.isfinite(samples).all()),
    }


def main(quick: bool = False):
    r = run(quick)
    print("metric,value")
    for k, v in r.items():
        print(f"{k},{v}")
    ok = r["ll_last_epoch"] > r["ll_first_epoch"] and \
        r["inpaint_mse"] < r["meanfill_mse"]
    print(f"# EM learns + inpainting beats mean-fill: {ok}")
    return r


if __name__ == "__main__":
    from repro.launch import compile_cache

    compile_cache.enable()
    main()

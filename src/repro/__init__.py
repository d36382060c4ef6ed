"""repro: Einsum Networks (Peharz et al., ICML 2020) as a JAX framework for
training and serving tractable probabilistic circuits on TPU.

Subpackages:
  core        the paper's contribution (einsum-layer PCs, autodiff-EM)
  kernels     Pallas TPU kernels + jnp oracles
  configs     architecture registry (--arch <id>)
  data        image datasets, synthetic data, sharded pipeline
  train       the compiled EM step and its training loop
  serve       the batched exact-inference engine
  mixture     mixtures of EiNets over k-means clusters
  eval        held-out metrics, inpainting, the image-eval workbench
  optim       AdamW with quantizable state, gradient compression
  checkpoint  atomic async checkpoints
  dist        sharding rules, fault tolerance, elasticity
  analysis    circuit/plan verifier, recompile sentry, AST lint
  obs         host tracing, metrics, device-side health telemetry
  launch      mesh, train/serve/eval drivers, compile cache
"""

__version__ = "1.0.0"

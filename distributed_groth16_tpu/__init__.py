"""distributed_groth16_tpu — a TPU-native collaborative Groth16 proving
framework (JAX/XLA/Pallas), providing the capabilities of the reference
zkSaaS prover (zkHubHQ/distributed-groth16): packed secret sharing, star
collectives, distributed NTT/MSM kernels, and the Groth16 prover/service
stack — re-designed for TPU meshes.

Layer map (mirrors SURVEY.md §1):
    ops/       field arithmetic, NTT, curve ops, MSM   (device kernels)
    parallel/  net collectives, PSS, d_fft/d_msm/d_pp  (the "mpc-net"+"dist-primitives" role)
    models/    groth16 prover/setup/verifier           (the "groth16" crate role)
    frontend/  circom r1cs/zkey/wtns readers, witness  (the "ark-circom" role)
    service/   proof-job queue, worker pool, CRS cache (docs/SERVICE.md)
    api/, cli  HTTP proving service + client           (the "mpc-api"/"zk-cli" role)
"""

import jax

# Persistent compilation cache: our kernels are built from deep uint32 limb
# graphs, and a cold proof is mostly compile. Placement is decided here,
# once per process, by utils/cache.py (JAX_COMPILATION_CACHE_DIR if the
# launcher set it, else a fixed <checkout>/.jax_cache).
from .utils.cache import setup_compile_cache

setup_compile_cache(jax)

__version__ = "0.1.0"

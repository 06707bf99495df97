"""atomo_tpu — TPU-native framework for communication-efficient distributed SGD
via atomic gradient sparsification.

A ground-up JAX/XLA/Pallas re-design of the capabilities of hwang595/ATOMO
(NeurIPS 2018): unbiased gradient compression (SVD atomic sparsification,
QSGD/TernGrad quantization, lossless packing) embedded in synchronous
data-parallel training — expressed as SPMD programs over a `jax.sharding.Mesh`
instead of an MPI parameter server.

Layer map (TPU-native analogue of reference SURVEY.md §1):
  codecs/    jit-compiled gradient compression kernels   (ref: src/codings/)
  models/    Flax model zoo                              (ref: src/model_ops/)
  training/  single-host + replicated trainers, optim    (ref: src/nn_ops.py,
             src/distributed_worker.py, src/sync_replicas_master_nn.py)
  parallel/  mesh, shard_map step functions, collectives (ref: mpi4py calls)
  data/      datasets + input pipeline                   (ref: src/datasets.py)
  utils/     metrics, logging, byte accounting           (ref: scattered)
  native/    C++ host-side runtime (lossless codec)      (ref: python-blosc)
"""

__version__ = "0.1.0"

from atomo_tpu.codecs import get_codec  # noqa: F401

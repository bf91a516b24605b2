"""distributed_embeddings_tpu: TPU-native distributed embedding framework.

A JAX/XLA/Pallas re-design of NVIDIA Merlin distributed-embeddings
(reference: /root/reference, v0.3.0) for TPU meshes: model-parallel embedding
tables sharded over a `jax.sharding.Mesh`, XLA all-to-all over ICI replacing
Horovod/NCCL, Pallas fused lookup kernels replacing the CUDA ops.

Top-level API parity with the reference package
(`distributed_embeddings/__init__.py:17-18`): ``embedding_lookup`` plus
``__version__``.
"""

from distributed_embeddings_tpu.ops.embedding_lookup import embedding_lookup
from distributed_embeddings_tpu.ops.ragged import RaggedBatch, SparseIds, row_to_split

__version__ = '0.2.0'

"""repro — production multi-pod JAX framework for the iCD paper.

Implements "A Generic Coordinate Descent Framework for Learning from
Implicit Feedback" (Bayer, Kanagal, He, Rendle, 2016) as a first-class
feature of a framework-scale training/inference system:

- ``repro.core``       — k-separable models, implicit regularizer, iCD solver
- ``repro.sparse``     — CSR / segment ops / EmbeddingBag / neighbor sampler
- ``repro.models``     — sharding-hint DSL for the model zoo (models/hints.py)
- ``repro.kernels``    — Pallas TPU kernels (gram, cd_update, cd_sweep,
                         topk_score) with pure-jnp oracles
- ``repro.optim``      — optimizers, schedules, gradient compression
- ``repro.train``      — train-step builders, remat, microbatching
- ``repro.serve``      — retrieval serving: the fault-tolerant mesh (one
                         device to sharded and replicated) / IVF tier
- ``repro.checkpoint`` — fault-tolerant sharded checkpointing
- ``repro.runtime``    — elastic mesh management, straggler watchdog
- ``repro.configs``    — assigned architecture configs + the paper's own
- ``repro.launch``     — production meshes, multi-pod dry-run, drivers
"""

__version__ = "1.0.0"

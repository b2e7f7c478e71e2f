"""repro — serverless-inspired BSP data engineering + LM training/serving in JAX.

Reproduction of "Combining Serverless and High-Performance Computing Paradigms
to support ML Data-Intensive Applications" (CS.DC 2025), adapted to TPU pods.

Public API re-exports the stable surface; submodules hold the substrate:

- ``repro.core``       communicator / BSP runtime / cost model (the paper's contribution)
- ``repro.dataframe``  distributed columnar tables (Cylon/DDMF analogue)
- ``repro.models``     the 10 assigned architectures
- ``repro.dist``       sharding rules, checkpointing, gradient compression
- ``repro.train`` / ``repro.serve``  step functions
- ``repro.launch``     mesh construction, multi-pod dry-run, drivers
- ``repro.kernels``    Pallas TPU kernels (+ jnp reference oracles)
"""

__version__ = "1.0.0"

from repro.core.communicator import (  # noqa: F401
    Communicator,
    CommEvent,
    CollectiveKind,
)

"""repro: a JAX/TPU reproduction of *PyG 2.0: Scalable Learning on Real World Graphs*.

Layers (bottom-up):
  kernels/      Pallas TPU kernels (+ jnp oracles) for the compute hot spots
  core/         the paper's contribution: EdgeIndex, message passing,
                aggregations, hetero transforms, trimming, explainability
  data/         FeatureStore / GraphStore / samplers / loaders (paper §2.3)
  nn/           GNN zoo + LM-architecture blocks (assigned-arch support)
  train/ serve/ step factories, optimizer, schedules, KV/SSM caches
  distributed/  sharding rules, checkpointing, elastic re-meshing
  launch/       production meshes, multi-pod dry-run, drivers
  configs/      assigned architecture configs (+ reduced smoke variants)
  trace.py      the program's spans and counters (off by default)
"""

__version__ = "2.0.0"

"""``repro.dist``: mesh sharding + distributed calibration subsystem.

Submodules:

  * ``sharding``  — PartitionSpec trees for params / train state / batches /
                    KV caches across every config in ``repro.configs``
  * ``calibrate`` — data-parallel Gram-free COALA calibration (butterfly
                    TSQR reduction of per-shard R factors)
"""

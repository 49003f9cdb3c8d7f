"""The survey's contribution — its taxonomy of distributed-DL techniques —
as first-class composable features:

  parallelism.py      §3.2   data/tensor sharding rules by parameter role
  pipeline.py         §3.2.3 GPipe micro-batch pipeline
  parameter_server.py §3.3.1 centralized architecture (TPU adaptation)
  allreduce.py        §3.3.1 decentralized topologies (ring/tree/butterfly)
  federated.py        §3.3.1 FedAvg
  sync.py             §3.3.2 BSP / SSP / ASP / SMA
  compression.py      §3.3.3 1-bit EF / TernGrad / QSGD / DGC
  comm_scheduler.py   §3.3.3 transfer scheduling (TicTac/Bosen model)
  precision.py        §3.3.3 mixed precision + stochastic rounding
"""
from repro.core.compression import Compressor, METHODS
from repro.core.sync import SimSyncEngine, SyncConfig, SyncEngine

__all__ = ["Compressor", "METHODS", "SimSyncEngine", "SyncConfig",
           "SyncEngine"]

"""Launchers: host-scale train/serve entry points, XLA host-device setup
(``env``), the hybrid training mesh, and the sharding-spec and roofline
helpers."""

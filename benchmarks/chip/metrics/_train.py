"""Shared reading of a train cell's trace."""
# the engine's jitted BSP step (``DeviceEngine._build_step``)
STEP = r"sharded_step"


def steps_in_window(run):
    tr = run.trace
    return len(tr.modules(tr.devices[0], STEP))

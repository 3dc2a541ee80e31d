"""Engine: host time per device dispatch in the window, from the engine's
phase counters: admit, pack, launch and harvest seconds (sync left out: the
host waits on the device there) over the dispatches, a rolled span counting
once.  None where the engine keeps no phase counters."""
from bench import readers

HOST_PHASES = ("admit_s", "pack_s", "launch_s", "harvest_s")


def read(record):
    st = readers.win(record).stats()
    if not all(k in st for k in HOST_PHASES):
        return None
    dispatches = st["steps"] - st["rolled_steps"] + st["rolled_dispatches"]
    return 1e3 * sum(st[k] for k in HOST_PHASES) / dispatches if dispatches else None

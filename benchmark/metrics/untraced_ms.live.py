"""Time of a refresh that no layer span names, in ms: each bench.query
span's length less the union of the program's leaf layer spans on its
thread inside it, averaged over the traced window's refreshes. The leaves
are LEAVES and every device.* span but device.execute, which only wraps
the device.* phases of the offload."""

from benchmark.spans import uncovered_ms

LEAVES = ("compile", "exec")


def is_leaf(name: str) -> bool:
    return name in LEAVES or (
        name.startswith("device.") and name != "device.execute"
    )


def read(run):
    return uncovered_ms(run.trace, is_leaf)
